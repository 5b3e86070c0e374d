"""Split learning (paper §1.2/§3.4) with the two training schedules:

* alternate-client (AC): prior art — clients take whole-dataset turns.
* alternate-minibatch (AM): the paper's proposed schedule — mini-batch turns.

Client segments are unique per client and never synchronized (paper: "We do
not use any form of weight synchronization").  The server segment (and its
Adam state) is shared and updated sequentially in schedule order — which is
why the compiled engine runs SL as a single scanned interleave over the
dense schedule array (``repro.core.schedule.schedule_array``) rather than a
vmap over hospitals: vmapping would break the exact sequential Adam
semantics of the shared server segment.
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from repro.core.schedule import SCHEDULES, schedule_array
from repro.core.strategies.base import (Strategy, EpochLog, make_split_step,
                                        np_batches)


class SplitLearning(Strategy):
    name = "sl"
    _sync_stacked = False     # SFLv2/v1 fold client averaging into the run

    def __init__(self, adapter, opt_factory, n_clients, schedule="ac",
                 transport=None, privacy=None, **kw):
        super().__init__(adapter, opt_factory, n_clients, privacy=privacy,
                         **kw)
        self.schedule = schedule
        self.transport = transport
        self.name = f"sl_{schedule}"
        if self.participation is not None:
            if self.participation.kind != "fixed":
                raise ValueError(
                    "the split family supports fixed-size participation "
                    "only (Participation(k=...)): the shared-server "
                    "schedule needs every slot filled")
            if self.observe is not None:
                raise ValueError("participation with observe is not "
                                 "supported for the split family")

    @contextlib.contextmanager
    def _account_span(self):
        """The ``account`` span of a compiled run.  With a
        transport it gets the run's wire counters from the transport's
        accounting inside it: ``wire_bytes`` and ``wire_bytes_raw`` (both
        legs, on the wire and as float32) and ``cut_arrays`` (the arrays
        that cross the cut per step)."""
        t = self.transport
        with self._span("account") as sp:
            if t is not None:
                wire, raw = t.bytes_on_wire, t.bytes_raw
            yield sp
            if sp is not None and t is not None:
                sp.set(wire_bytes=int(t.bytes_on_wire - wire),
                       wire_bytes_raw=int(t.bytes_raw - raw),
                       cut_arrays=t.cut_arrays(self.adapter))

    def _client_tree(self, params):
        t = {"front": params["front"]}
        if self.adapter.nls:
            t["tail"] = params["tail"]
        return t

    def setup(self, key):
        import jax
        keys = jax.random.split(key, self.n_clients)
        if not hasattr(self, "_opt_c"):
            self._opt_c, self._opt_s = self.opt_factory(), self.opt_factory()
            self._step = make_split_step(self.adapter, self._opt_c,
                                         self._opt_s, self.transport,
                                         self.privacy)
        opt_c, opt_s = self._opt_c, self._opt_s
        clients, c_opts = [], []
        server = None
        for k in keys:
            params = self.adapter.init(k)
            ct = self._client_tree(params)
            clients.append(ct)
            c_opts.append(opt_c.init(ct))
            if server is None:
                server = params["middle"]
        return {"clients": clients, "server": server,
                "c_opts": c_opts, "s_opt": opt_s.init(server)}

    def _round_telemetry(self, tel, losses, metrics, sched):
        """Reduce one epoch's schedule-ordered per-step taps."""
        from repro.obs import telemetry as T
        if not len(sched):
            return T.RoundTelemetry(0, {})
        return T.rounds_scheduled(
            tel, np.asarray(losses, np.float64)[None],
            {k: np.asarray(v, np.float64)[None]
             for k, v in metrics.items()},
            np.asarray(sched), self.n_clients)[0]

    def _run_epoch_stepwise(self, state, client_data, rng, batch_size):
        tel = self._tel
        step = self._step if tel is None else self._get_obs(
            "_step_obs", tel,
            lambda: make_split_step(self.adapter, self._opt_c, self._opt_s,
                                    self.transport, self.privacy, tel))
        batches = [np_batches(d, batch_size, rng, self.drop_remainder)
                   for d in client_data]
        order = SCHEDULES[self.schedule]([len(b) for b in batches])
        losses, loss_w, met_vals = [], [], []
        client_steps = [0] * self.n_clients
        for c, b in order:
            args = (state["clients"][c], state["server"],
                    state["c_opts"][c], state["s_opt"], batches[c][b])
            if self._keyed:
                args = args + (self._next_key(),)
            out = step(*args)
            self._count_dispatch()
            (state["clients"][c], state["server"], state["c_opts"][c],
             state["s_opt"], loss) = out[0], out[1], out[2], out[3], out[4]
            if tel is not None:
                met_vals.append(out[5])
            losses.append(float(loss))
            loss_w.append(len(batches[c][b]["label"]))
            client_steps[c] += 1
            self._dp_account(c, len(client_data[c]["label"]), batch_size)
            if self.transport is not None:
                self.transport.account(self.adapter, batches[c][b])
        if order:
            self._record_wire_epoch(
                next(bs[0] for bs in batches if bs),
                [len(b) for b in batches])
        self._end_of_epoch(state)
        log = EpochLog(losses, len(losses), weights=loss_w,
                       client_steps=client_steps)
        if tel is not None:
            log.telemetry = self._round_telemetry(
                tel, losses,
                {k: [float(m[k]) for m in met_vals]
                 for k in (met_vals[0] if met_vals else {})}, order)
        return state, log

    def _ensure_stacked(self, state):
        """Compiled SL/SFLv2 state keeps the hospital axis stacked BETWEEN
        epochs too — unstacking n_clients x n_leaves every epoch costs more
        host time than the compiled epoch itself.  Under placement the
        stack is padded to the mesh multiple (phantom rows are copies of
        the last real client; the schedule never touches them and syncs
        weight them zero) and placed on the "hosp" mesh."""
        from repro.core.partition import stack_trees
        place = self.placement
        if "stacked_clients" not in state:
            state["stacked_clients"] = place.pad_tree(
                stack_trees(state.pop("clients")))
            state["stacked_c_opts"] = place.pad_tree(
                stack_trees(state.pop("c_opts")))
        state["stacked_clients"] = place.put(state["stacked_clients"])
        state["stacked_c_opts"] = place.put(state["stacked_c_opts"])

    def _run_compiled(self, state, client_data, rng, batch_size, n_epochs):
        from repro.core.strategies import engine as ENG
        if self.participation is not None:
            return self._run_participation(state, client_data, rng,
                                           batch_size, n_epochs)
        tel = self._tel
        place = self.placement
        with self._span("pack") as sp:
            inputs, example, packed, counters = ENG.pack_run_inputs(
                client_data, batch_size, rng, n_epochs, self.drop_remainder,
                place, span=self._span)
            if sp is not None:
                sp.set(**counters)
        sched = schedule_array(self.schedule, packed.n_batches)
        sync_w = place.client_weights() if place.padded else None
        build = functools.partial(
            ENG.make_interleaved_run, self.adapter, self._opt_c,
            self._opt_s, self.transport, self.privacy,
            sync_clients=self._sync_stacked, client_weights=sync_w,
            placement=place)
        if tel is None:
            if not hasattr(self, "_run_c"):
                self._run_c = build()
            run_fn = self._run_c
        else:
            run_fn = self._get_obs("_run_obs_c", tel,
                                   lambda: build(telemetry=tel))
        key_idx = np.stack([
            self._take_key_indices(len(sched)) if self._keyed
            else np.zeros((len(sched),), np.uint32)
            for _ in range(n_epochs)])
        self._ensure_stacked(state)
        args = (state["stacked_clients"], state["server"],
                state["stacked_c_opts"], state["s_opt"],
                *place.put(inputs, axis=1), place.put(packed.ex_weights),
                sched, key_idx, self._privacy_base_key())
        out = self._enqueue(run_fn, args)
        (state["stacked_clients"], state["server"],
         state["stacked_c_opts"], state["s_opt"], losses) = out[:5]
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._account_span():
            state["stacked_clients"] = place.put(state["stacked_clients"])
            state["stacked_c_opts"] = place.put(state["stacked_c_opts"])
            logs = []
            for e in range(n_epochs):
                flat, loss_w = ENG.scheduled_log(losses[e], sched, packed)
                logs.append(EpochLog(flat, len(flat), weights=loss_w,
                                     client_steps=list(
                                         packed.n_batches[:self.n_clients])))
            if tel is not None:
                from repro.obs import telemetry as T
                rounds = T.rounds_scheduled(
                    tel, losses,
                    {k: np.asarray(v) for k, v in out[5].items()},
                    sched, self.n_clients)
                for log, r in zip(logs, rounds):
                    log.telemetry = r
            self._account_compiled(example, packed, batch_size, n_epochs)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, inputs, example, packed, out
        return state, logs

    def _run_participation(self, state, client_data, rng, batch_size,
                           n_epochs):
        """Whole participating SL/SFLv2 run: per round the full-N virtual
        schedule is filtered to the K sampled hospitals (relative order
        preserved) and padded to a fixed step count; per-step keys carry
        the VIRTUAL full-N schedule position, so a hospital's noise draws
        depend only on (round, hospital) and ``Participation(k=N)``
        reproduces ``participation=None`` exactly."""
        from repro.core.strategies import engine as ENG
        if self._tel is not None:
            raise ValueError("participation with observe is not supported "
                             "for the split family")
        part = self.participation
        with self._span("pack") as sp:
            batches, pack = ENG.pack_participation_run(
                client_data, batch_size, rng, n_epochs, part,
                self.drop_remainder, span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(batches, pack.mask.size,
                                           pack.mask.sum()))
        nbs = pack.n_batches
        full_sched = schedule_array(self.schedule, nbs)
        S_N = len(full_sched)
        # per-round schedule rows (slot, batch, valid) + virtual key pos
        rounds = []
        for e in range(n_epochs):
            gid = pack.slot_gid[e]
            slot_of = {int(g): s for s, g in enumerate(gid) if g >= 0}
            rounds.append([(slot_of[int(c)], int(b), p)
                           for p, (c, b) in enumerate(full_sched)
                           if int(c) in slot_of])
        steps_max = max((len(r) for r in rounds), default=0)
        if steps_max == 0:
            return state, []       # no sampled hospital has a batch
        sched = np.zeros((n_epochs, steps_max, 3), np.int32)
        key_idx = np.zeros((n_epochs, steps_max), np.uint32)
        base0 = self._key_step
        for e, rows in enumerate(rounds):
            for t, (slot, b, p) in enumerate(rows):
                sched[e, t] = (slot, b, 1)
                if self._keyed:
                    key_idx[e, t] = base0 + 1 + e * S_N + p
        if self._keyed:
            self._key_step += n_epochs * S_N
        if not hasattr(self, "_run_part_c"):
            self._run_part_c = ENG.make_interleaved_run_participation(
                self.adapter, self._opt_c, self._opt_s, self.n_clients,
                self.transport, self.privacy,
                sync_clients=self._sync_stacked)
        run_fn = self._run_part_c
        self._ensure_stacked(state)
        args = (state["stacked_clients"], state["server"],
                state["stacked_c_opts"], state["s_opt"], batches,
                pack.ex_weights, sched, key_idx,
                self._privacy_base_key(), pack.slot_gid)
        out = self._enqueue(run_fn, args)
        (state["stacked_clients"], state["server"],
         state["stacked_c_opts"], state["s_opt"], losses) = out[:5]
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._account_span():
            logs = self._account_participation(
                losses, rounds, pack, part, batch_size, n_epochs, batches)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, batches, pack, out
        return state, logs

    def _account_participation(self, losses, rounds, pack, part,
                               batch_size, n_epochs, batches):
        """Epoch logs, DP and wire accounting of a participating run."""
        nbs = pack.n_batches
        logs = []
        for e, rows in enumerate(rounds):
            gid = pack.slot_gid[e]
            flat = [float(x) for x in losses[e, :len(rows)]]
            loss_w = [pack.step_examples[int(gid[slot])][b]
                      for slot, b, _p in rows]
            csteps = [0] * pack.n_global
            for slot, _b, _p in rows:
                csteps[int(gid[slot])] += 1
            logs.append(EpochLog(flat, len(flat), weights=loss_w,
                                 client_steps=csteps))
        # amplified RDP: every hospital composes every round at rate K/N
        # over the steps it runs when sampled
        self._last_part_nbs = list(nbs)
        for g in range(pack.n_global):
            if nbs[g]:
                self._dp_account(g, pack.n_samples[g], batch_size,
                                 count=nbs[g] * n_epochs,
                                 q_scale=part.rate)
        # wire: only sampled clients' transfers exist, per round
        if self.transport is not None:
            example = {k: v[0, 0, 0] for k, v in batches.items()}
            for e in range(n_epochs):
                ids = np.flatnonzero(pack.part_mask[e])
                counts = [0] * pack.n_global
                for g in ids:
                    g = int(g)
                    counts[g] = nbs[g]
                    for m, n_steps in zip(
                            *np.unique(pack.step_examples[g],
                                       return_counts=True)):
                        b = (example if m == pack.batch_size
                             else {k: v[:m] for k, v in example.items()})
                        self.transport.account(self.adapter, b,
                                               count=int(n_steps))
                self._record_wire_epoch(example, counts, client_set=ids)
        return logs

    def _account_compiled(self, example, packed, batch_size, n_epochs):
        """Analytic accounting for the compiled path: the DP accountant
        composes each hospital's step count in one call, and the transport
        meters each step at its TRUE batch shape — full batches in one
        ``count=`` call, a kept remainder batch (``drop_remainder=False``)
        at its short shape, exactly the bytes the stepwise per-step path
        meters — times ``n_epochs`` for a whole-run program.  ``example``
        is a full batch; only its shapes are read."""
        for c, nb in enumerate(packed.n_batches):
            if not nb:
                continue
            self._dp_account(c, packed.n_samples[c], batch_size,
                             count=nb * n_epochs)
            if self.transport is not None:
                for m, n_steps in zip(*np.unique(packed.step_examples[c],
                                                 return_counts=True)):
                    b = (example if m == packed.batch_size
                         else {k: v[:m] for k, v in example.items()})
                    self.transport.account(self.adapter, b,
                                           count=int(n_steps) * n_epochs)
        for _ in range(n_epochs):
            self._record_wire_epoch(example, packed.n_batches)

    def _record_wire_epoch(self, example_batch, n_batches,
                           client_set=None):
        """The analytic->timeline bridge hook: hand the transport this
        epoch's schedule signature so ``wire.simulator`` can expand the
        summary accounting back into per-step timelines.  Placement
        phantom rows (zero batches) are sliced off — the recorded
        signature is placement-independent.  ``client_set`` marks a
        participating round's sampled clients (unsampled entries are
        zero, so the expansion emits no events for them)."""
        n_batches = list(n_batches)[:self.n_clients]
        if self.transport is None or not sum(n_batches):
            return
        self.transport.record_epoch(self.adapter, example_batch,
                                    self.name.rsplit("_", 1)[0],
                                    self.schedule, n_batches,
                                    client_set=client_set)

    def _empty_epoch(self, state, client_data, rng, batch_size):
        log = super()._empty_epoch(state, client_data, rng, batch_size)
        self._end_of_epoch(state)              # SFLv2 still syncs clients
        return log

    def _end_of_epoch(self, state):
        pass

    def params_for_eval(self, state, client_idx):
        if "stacked_clients" in state:           # compiled-engine layout
            from repro.core.partition import tree_take
            ct = tree_take(state["stacked_clients"], client_idx)
        else:
            ct = state["clients"][client_idx]
        p = {"front": ct["front"], "middle": state["server"]}
        if self.adapter.nls:
            p["tail"] = ct["tail"]
        return p
