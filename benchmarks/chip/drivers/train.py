"""Training traffic: whole ``Strategy.run`` calls, one epoch each.

Set-up builds the strategy once from the configuration and the mix, fills
the state it expects with the seed's weights, and makes its first run:
that run compiles (or loads) the program, and its losses and the state it
returns are what the reference checks.  The window then calls the same
``Strategy.run`` on the same data, each run another shuffle, until
``--seconds`` have passed; the run in progress then is finished and
counted.  Each run packs on the host, dispatches once and reads its
losses back, all inside the time.

``train_images_per_s``: training examples with nonzero weight (every
hospital's batch of every step) over the window.  ``--trace 1`` traces
``trace_runs`` whole runs instead.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from chip import gen, weights
from chip.compare import train_numbers
from chip.ref_train import Trajectory, epoch_batches, schedule

RUN_STREAM = 4          # the seed's stream that shuffles every epoch


def _fill(abstract: dict, fronts, server, n: int) -> dict:
    """The program's state layout with the seed's weights in it and every
    other leaf (the optimizer's) zero."""
    def zeros(tree):
        return jax.tree.map(lambda a: jax.numpy.zeros(a.shape, a.dtype), tree)

    state = {}
    for k, v in abstract.items():
        if k == "stacked_clients":
            state[k] = {"front": fronts}
        elif k == "clients":
            state[k] = [{"front": jax.tree.map(lambda a, c=c: a[c], fronts)}
                        for c in range(n)]
        elif k == "server":
            state[k] = server
        else:
            state[k] = zeros(v)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), state)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
    if got != want:
        raise ValueError("the seed's weights do not fit the program's state")
    return state


def _snapshot(state: dict) -> dict:
    """Host copies of the segments and the optimizers' first moments."""
    from chip.ref_train import B1
    if "stacked_c_opts" in state:
        c_opt = state["stacked_c_opts"]
    else:
        c_opt = state["c_opt"]

    def mhat(opt):
        t = np.asarray(opt["step"], np.float64)
        # no step taken: no moment to correct
        c = np.where(t > 0, 1 - B1 ** t, 1.0)
        return jax.tree.map(lambda m: np.asarray(m) / c.reshape(
            t.shape + (1,) * (m.ndim - t.ndim)), opt["mu"])

    return {"fronts": jax.tree.map(np.asarray,
                                   state["stacked_clients"]["front"]),
            "server": jax.tree.map(np.asarray, state["server"]),
            "mhat_fronts": mhat(c_opt), "mhat_server": mhat(state["s_opt"])}


class Cell:
    def __init__(self, ctx):
        from repro import optim as O
        from repro.core.strategies import make_strategy
        from repro.wire import Transport
        self.ctx = ctx
        cfg, mix = ctx.cfg, ctx.mix
        if int(mix["epochs_per_run"]) != 1:
            raise ValueError("the reference follows one epoch per run")
        self.sizes = list(mix["train_per_client"])
        self.batch = int(mix["batch"])
        n = len(self.sizes)
        clients = gen.cxr_clients(ctx.seed, self.sizes, cfg["image_size"])
        self.data = [c["train"] for c in clients]
        fronts, server = weights.make(ctx.family, cfg["model"], ctx.seed, n)
        self.init = {"fronts": jax.tree.map(np.asarray, fronts),
                     "server": jax.tree.map(np.asarray, server)}
        link = mix["link"]
        self.strat = make_strategy(
            mix["method"], ctx.program_adapter(),
            lambda: O.adam(mix["lr"]), n,
            transport=None if link == "identity" else Transport(link))
        abstract = jax.eval_shape(self.strat.setup, jax.random.key(0))
        self.state = _fill(abstract, fronts, server, n)
        self.rng = gen.seed_rng(ctx.seed, RUN_STREAM)
        losses, self.images_per_run = self._run()
        self.first = dict(_snapshot(self.state), losses=losses)

    def _run(self):
        """One whole run; returns its losses (steps x hospitals for the
        synchronous methods, steps otherwise) and its training examples."""
        self.state, logs = self.strat.run(self.state, self.data, self.rng,
                                          self.batch,
                                          int(self.ctx.mix["epochs_per_run"]))
        jax.block_until_ready(self.state)
        log = logs[0]
        losses = np.asarray(log.losses, np.float64)
        if self.ctx.mix["method"].startswith("sflv3"):
            losses = losses.reshape(log.steps, len(self.sizes))
        images = sum(sum(lg.client_steps) for lg in logs) * self.batch
        return losses, images

    def window(self) -> dict:
        ctx = self.ctx
        spans = []
        if ctx.trace:
            from repro.obs.trace import Tracer
            tracer = self.strat.attach_tracer(Tracer())
            with ctx.traced():
                t0 = time.perf_counter()
                for _ in range(int(ctx.mix["trace_runs"])):
                    self._run()
                t1 = time.perf_counter()
            self.strat.attach_tracer(None)
            base = time.perf_counter() - tracer.now()
            spans = [(e["name"], base + e["ts"] * 1e-6,
                      base + (e["ts"] + e["dur"]) * 1e-6)
                     for e in tracer.events if e.get("ph") == "X"]
            runs = int(ctx.mix["trace_runs"])
        else:
            t0 = time.perf_counter()
            runs = 0
            while True:
                self._run()
                runs += 1
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            t1 = time.perf_counter()
        images = runs * self.images_per_run
        return {"attempted": runs, "failed": 0, "span": (t0, t1),
                "images": images, "host_spans": spans,
                "e2e": {"train_images_per_s": images / (t1 - t0)}}

    def release(self):
        self.state = None

    def check(self) -> dict:
        """The reference follows the first run from the same weights on
        the same batches; returns the compared numbers."""
        ctx, mix = self.ctx, self.ctx.mix
        return train_numbers(self.first, reference_run(
            ctx.family, ctx.cfg["model"], mix, self.data, self.init,
            ctx.seed, "highest"), self.init)


def reference_run(family, model, mix, data, init, seed, prec,
                  half_batch=False) -> dict:
    """The reference's first run, from ``init``, in precision ``prec``
    (``half_batch``: with that fault planted)."""
    rng = gen.seed_rng(seed, RUN_STREAM)
    sizes = [len(d["label"]) for d in data]
    orders = epoch_batches(rng, sizes, int(mix["batch"]))
    rows = schedule(mix["method"], [len(o) for o in orders])
    traj = Trajectory(family, model, mix["link"], mix["lr"], prec,
                      half_batch)
    out = traj.run(jax.device_put(init["fronts"]),
                   jax.device_put(init["server"]), data, orders, rows)
    return jax.tree.map(np.asarray, out)
