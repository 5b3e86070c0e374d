"""SplitFed variants.

* SFLv2 (Thapa et al.): server segment trained SEQUENTIALLY like SL, but the
  client segments are synchronized at the end of each epoch by fed-averaging.
* SFLv3 (THE PAPER'S PROPOSAL, Algorithm 1): client segments stay unique
  (like SL), while the server segment is updated with the weighted AVERAGE of
  per-client gradients computed in parallel — removing the sequential
  catastrophic-forgetting bias of SL/SFLv2 server training.
* SFLv1 (bonus; the paper excluded it for hardware reasons): SFLv3's parallel
  server + fed-averaged client segments each round.

SFLv3 implementation note (recorded in DESIGN.md): Algorithm 1 as printed
concatenates a full epoch of activations and performs one server update per
round; trained with Adam@1e-4 for 10 rounds that cannot reach the reported
AUROC. We use the batch-synchronous reading (one averaged server update per
mini-batch step, "same as SplitFedv1" per the paper's own description),
which matches the reported training times and accuracies.

Under the compiled engine SFLv2 inherits SL's scanned interleave (its server
is sequential too); SFLv3/v1 scan over synchronous steps with the vmapped
per-client step inside and the wrap-around batch index precomputed as a
dense ``[steps, n_clients]`` array.
"""

from __future__ import annotations

import numpy as np

from repro.core.partition import stack_trees
from repro.core.strategies.base import (EpochLog, make_sflv3_step,
                                        np_batches, tree_mean)
from repro.core.strategies.split import SplitLearning


class SplitFedV2(SplitLearning):
    """Sequential server training + end-of-epoch client averaging."""

    _sync_stacked = True      # fold the client averaging into the run scan

    def __init__(self, adapter, opt_factory, n_clients, schedule="ac",
                 transport=None, privacy=None, **kw):
        super().__init__(adapter, opt_factory, n_clients, schedule,
                         transport, privacy, **kw)
        self.name = f"sflv2_{schedule}"

    def _end_of_epoch(self, state):
        if "stacked_clients" in state:           # compiled-engine layout
            from repro.core.strategies.engine import stacked_mean_sync
            place = self.placement
            state["stacked_clients"] = stacked_mean_sync(
                state["stacked_clients"],
                place.client_weights() if place.padded else None)
            return
        avg = tree_mean(state["clients"])
        state["clients"] = [avg for _ in range(self.n_clients)]


class SplitFedV3(SplitLearning):
    """Unique clients + gradient-averaged parallel server updates (Alg. 1)."""

    def __init__(self, adapter, opt_factory, n_clients, schedule="ac",
                 transport=None, privacy=None, **kw):
        super().__init__(adapter, opt_factory, n_clients, schedule,
                         transport, privacy, **kw)
        if not self.drop_remainder:
            raise ValueError(
                "SplitFedV3/V1 are batch-synchronous: every client ships a "
                "same-shaped batch each step, so drop_remainder=False is "
                "not representable; use drop_remainder=True")
        self.name = f"sflv3_{schedule}"

    def setup(self, key):
        import jax
        keys = jax.random.split(key, self.n_clients)
        if not hasattr(self, "_opt_c"):
            self._opt_c, self._opt_s = self.opt_factory(), self.opt_factory()
            self._step3 = make_sflv3_step(self.adapter, self._opt_c,
                                          self._opt_s, self.n_clients,
                                          self.transport, self.privacy)
        opt_c, opt_s = self._opt_c, self._opt_s
        clients, server = [], None
        for k in keys:
            params = self.adapter.init(k)
            clients.append(self._client_tree(params))
            if server is None:
                server = params["middle"]
        stacked = stack_trees(clients)
        if self.engine == "compiled":
            # placement layout: phantom rows (copies of the last real
            # client) reach the mesh multiple; their batches are zeros and
            # their weight in every average is zero
            stacked = self.placement.put(self.placement.pad_tree(stacked))
        return {"stacked_clients": stacked, "server": server,
                "c_opt": opt_c.init(stacked), "s_opt": opt_s.init(server)}

    def _check_batches(self, n_batches, batch_size):
        empty = [c for c, nb in enumerate(n_batches) if not nb]
        if empty:
            # batch-synchronous SFLv3 averages over ALL clients every step;
            # a client without a single full batch cannot participate
            raise ValueError(
                f"clients {empty} have fewer than batch_size="
                f"{batch_size} train samples; SplitFedV3 needs at least "
                "one batch per client")

    def _sync_round_telemetry(self, tel, losses, metrics):
        """Reduce one epoch's ``[S, C]`` synchronous-step taps."""
        from repro.obs import telemetry as T
        losses = np.asarray(losses, np.float64)
        if not losses.size:
            return T.RoundTelemetry(0, {})
        return T.rounds_sync(
            tel, losses[None],
            {k: np.asarray(v, np.float64)[None]
             for k, v in metrics.items()}, self.n_clients)[0]

    def _run_epoch_stepwise(self, state, client_data, rng, batch_size):
        tel = self._tel
        step3 = self._step3 if tel is None else self._get_obs(
            "_step3_obs", tel,
            lambda: make_sflv3_step(self.adapter, self._opt_c, self._opt_s,
                                    self.n_clients, self.transport,
                                    self.privacy, telemetry=tel))
        batches = [np_batches(d, batch_size, rng) for d in client_data]
        self._check_batches([len(b) for b in batches], batch_size)
        steps = max(len(b) for b in batches)
        losses, step_loss_rows, met_vals = [], [], []
        for s in range(steps):
            # clients that exhausted their data wrap around (all data is
            # seen once per epoch; the server always averages n clients)
            stacked_batch = stack_trees(
                [batches[c][s % len(batches[c])] for c in
                 range(self.n_clients)])
            args = (state["stacked_clients"], state["server"],
                    state["c_opt"], state["s_opt"], stacked_batch)
            if self._keyed:
                args = args + (self._next_key(),)
            out = step3(*args)
            self._count_dispatch()
            (state["stacked_clients"], state["server"], state["c_opt"],
             state["s_opt"], step_losses) = out[:5]
            if tel is not None:
                step_loss_rows.append(np.asarray(step_losses))
                met_vals.append(out[5])
            losses.extend(np.asarray(step_losses).tolist())
            for c in range(self.n_clients):
                # wrap-around resampling included: every client is touched
                self._dp_account(c, len(client_data[c]["label"]),
                                 batch_size)
            if self.transport is not None:
                # every client transfers every step (wrap-around included)
                for c in range(self.n_clients):
                    self.transport.account(self.adapter,
                                           batches[c][s % len(batches[c])])
        self._record_wire_epoch(batches[0][0], [len(b) for b in batches])
        self._end_of_epoch(state)
        log = EpochLog(losses, steps,
                       client_steps=[steps] * self.n_clients)
        if tel is not None:
            log.telemetry = self._sync_round_telemetry(
                tel, np.stack(step_loss_rows),
                {k: np.stack([np.asarray(m[k]) for m in met_vals])
                 for k in (met_vals[0] if met_vals else {})})
        return state, log

    def _account_v3(self, example, packed, batch_size, n_epochs):
        """Analytic accounting: every client is touched every synchronous
        step (wrap-around resampling included), so the per-epoch count is
        simply ``steps = nb_max`` for DP and transport alike.  ``example``
        is a full batch; only its shapes are read."""
        steps = packed.nb_max
        for c in range(self.n_clients):
            self._dp_account(c, packed.n_samples[c], batch_size,
                             count=steps * n_epochs)
            if self.transport is not None:
                self.transport.account(self.adapter, example,
                                       count=steps * n_epochs)
        for _ in range(n_epochs):
            self._record_wire_epoch(example, packed.n_batches)

    def _empty_epoch(self, state, client_data, rng, batch_size):
        # batch-synchronous: a run without a batch has no hospital with one
        self._check_batches([0] * self.n_clients, batch_size)

    def _run_compiled(self, state, client_data, rng, batch_size, n_epochs):
        from repro.core.strategies import engine as ENG
        if self.participation is not None:
            return self._run_participation(state, client_data, rng,
                                           batch_size, n_epochs)
        tel = self._tel
        place = self.placement
        with self._span("pack") as sp:
            inputs, example, packed, counters = ENG.pack_run_inputs(
                client_data, batch_size, rng, n_epochs, True, place,
                span=self._span)
            if sp is not None:
                sp.set(**counters)
        self._check_batches(packed.n_batches[:self.n_clients], batch_size)
        steps = packed.nb_max
        if tel is None:
            if not hasattr(self, "_run3_c"):
                self._run3_c = ENG.make_sflv3_run(
                    self.adapter, self._opt_c, self._opt_s, place.c_pad,
                    self.transport, self.privacy,
                    sync_clients=self._sync_stacked,
                    client_weights=(place.client_weights() if place.padded
                                    else None),
                    placement=place)
            run_fn = self._run3_c
        else:
            run_fn = self._get_obs(
                "_run3_obs_c", tel,
                lambda: ENG.make_sflv3_run(
                    self.adapter, self._opt_c, self._opt_s, place.c_pad,
                    self.transport, self.privacy,
                    sync_clients=self._sync_stacked,
                    client_weights=(place.client_weights() if place.padded
                                    else None),
                    placement=place, telemetry=tel))
        b_idx = np.stack([[s % nb if nb else 0 for nb in packed.n_batches]
                          for s in range(steps)]).astype(np.int32)
        key_idx = np.stack([
            self._take_key_indices(steps) if self._keyed
            else np.zeros((steps,), np.uint32) for _ in range(n_epochs)])
        args = (place.put(state["stacked_clients"]), state["server"],
                place.put(state["c_opt"]), state["s_opt"],
                *place.put(inputs, axis=1), place.put(b_idx, axis=1),
                key_idx, self._privacy_base_key())
        out = self._enqueue(run_fn, args)
        (state["stacked_clients"], state["server"], state["c_opt"],
         state["s_opt"], losses) = out[:5]
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._account_span():
            logs = [EpochLog(
                losses[e, :, :self.n_clients].reshape(-1).tolist(), steps,
                client_steps=[steps] * self.n_clients)
                for e in range(n_epochs)]
            if tel is not None:
                from repro.obs import telemetry as T
                rounds = T.rounds_sync(
                    tel, losses,
                    {k: np.asarray(v) for k, v in out[5].items()},
                    self.n_clients)
                for log, r in zip(logs, rounds):
                    log.telemetry = r
            self._account_v3(example, packed, batch_size, n_epochs)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, inputs, example, packed, out
        return state, logs

    def _run_participation(self, state, client_data, rng, batch_size,
                           n_epochs):
        """Whole participating SplitFedv3/v1 run: K sampled hospitals step
        batch-synchronously each round; client segments and optimizer rows
        are gathered/scattered out of the persistent ``[N, ...]`` stacks
        by global id inside the fused program.

        The steps axis is fixed at the GLOBAL max batch count so the grid
        never reshapes; rounds whose sampled cohort is shallower mask the
        tail steps out.  Per-step keys use the full-N virtual grid
        (round-major), so ``Participation(k=N)`` reproduces
        ``participation=None`` exactly.  The RDP accountant composes
        every hospital every round at the amplified rate over the GLOBAL
        step count — a (documented) conservative bound when a sampled
        cohort runs fewer steps."""
        from repro.core.strategies import engine as ENG
        if self._tel is not None:
            raise ValueError("participation with observe is not supported "
                             "for the split family")
        part = self.participation
        with self._span("pack") as sp:
            batches, pack = ENG.pack_participation_run(
                client_data, batch_size, rng, n_epochs, part, True,
                span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(batches, pack.mask.size,
                                           pack.mask.sum()))
        nbs = pack.n_batches
        self._check_batches(nbs, batch_size)
        NB_N, S = pack.nb_max, pack.n_slots
        b_idx = np.zeros((n_epochs, NB_N, S), np.int32)
        step_valid = np.zeros((n_epochs, NB_N), np.float32)
        key_idx = np.zeros((n_epochs, NB_N), np.uint32)
        base0 = self._key_step
        real_steps = []
        for e in range(n_epochs):
            gid = pack.slot_gid[e]
            rs = max(nbs[int(g)] for g in gid if g >= 0)
            real_steps.append(rs)
            step_valid[e, :rs] = 1.0
            for s in range(S):
                g = int(gid[s])
                if g >= 0 and nbs[g]:
                    b_idx[e, :, s] = np.arange(NB_N) % nbs[g]
            if self._keyed:
                key_idx[e] = base0 + 1 + e * NB_N + np.arange(NB_N)
        if self._keyed:
            self._key_step += n_epochs * NB_N
        if not hasattr(self, "_run3_part_c"):
            self._run3_part_c = ENG.make_sflv3_run_participation(
                self.adapter, self._opt_c, self._opt_s, S, self.n_clients,
                self.transport, self.privacy,
                sync_clients=self._sync_stacked)
        run_fn = self._run3_part_c
        args = (state["stacked_clients"], state["server"], state["c_opt"],
                state["s_opt"], batches, b_idx, key_idx, step_valid,
                self._privacy_base_key(), pack.slot_gid)
        out = self._enqueue(run_fn, args)
        (state["stacked_clients"], state["server"], state["c_opt"],
         state["s_opt"], losses) = out[:5]
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._account_span():
            logs = self._account_participation(
                losses, pack, part, real_steps, batch_size, n_epochs,
                batches)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, batches, pack, out
        return state, logs

    def _account_participation(self, losses, pack, part, real_steps,
                               batch_size, n_epochs, batches):
        """Epoch logs, DP and wire accounting of a participating run."""
        nbs = pack.n_batches
        NB_N = pack.nb_max
        logs = []
        for e in range(n_epochs):
            rs = real_steps[e]
            sampled = set(int(g) for g in pack.slot_gid[e] if g >= 0)
            csteps = [rs if g in sampled else 0
                      for g in range(pack.n_global)]
            logs.append(EpochLog(losses[e, :rs, :].reshape(-1).tolist(),
                                 rs, client_steps=csteps))
        # amplified RDP at the global step count (conservative when a
        # sampled cohort runs fewer); wire sees sampled clients only
        self._last_part_nbs = [NB_N] * pack.n_global
        for g in range(pack.n_global):
            self._dp_account(g, pack.n_samples[g], batch_size,
                             count=NB_N * n_epochs, q_scale=part.rate)
        if self.transport is not None:
            example = {k: v[0, 0, 0] for k, v in batches.items()}
            for e in range(n_epochs):
                ids = np.flatnonzero(pack.part_mask[e])
                rs = real_steps[e]
                counts = [0] * pack.n_global
                for g in ids:
                    counts[int(g)] = rs
                    self.transport.account(self.adapter, example, count=rs)
                self._record_wire_epoch(example, counts, client_set=ids)
        return logs

    def _end_of_epoch(self, state):
        pass

    def params_for_eval(self, state, client_idx):
        from repro.core.partition import tree_take
        ct = tree_take(state["stacked_clients"], client_idx)
        p = {"front": ct["front"], "middle": state["server"]}
        if self.adapter.nls:
            p["tail"] = ct["tail"]
        return p


class SplitFedV1(SplitFedV3):
    """Parallel server (like v3) + fed-averaged clients each round."""

    _sync_stacked = True

    def __init__(self, adapter, opt_factory, n_clients, schedule="ac",
                 transport=None, privacy=None, **kw):
        super().__init__(adapter, opt_factory, n_clients, schedule,
                         transport, privacy, **kw)
        self.name = f"sflv1_{schedule}"

    def _end_of_epoch(self, state):
        from repro.core.strategies.engine import stacked_mean_sync
        place = self.placement
        state["stacked_clients"] = stacked_mean_sync(
            state["stacked_clients"],
            place.client_weights() if place.padded else None)
