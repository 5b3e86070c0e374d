"""Federated learning with FedAvg (paper §1.1/§3.3).

One federated round == one epoch (as in the paper): the global model is
pushed to every client, each client runs one local epoch with its own Adam,
and the server aggregates the resulting parameters with a data-size-weighted
average (McMahan et al. federated averaging).

With ``privacy.dp_enabled`` every local step uses the DP-SGD estimator and
each hospital's accountant composes over its own rounds; with
``privacy.secagg`` the aggregation runs through pairwise-mask secure
aggregation (``repro.privacy.secagg``) — the server only ever adds
uniformly-masked fixed-point uploads, and the handshake + masked-upload
bytes are metered.

Local epochs are independent across hospitals, so the compiled engine runs
the whole round as ONE program: ``vmap`` over the hospital axis of a
``lax.scan`` over each hospital's padded batch grid.  A multi-round
``run(n_epochs)`` goes further and folds the weighted FedAvg aggregation
into an outer scan over rounds — the whole training run is one XLA call.
Secure aggregation keeps the per-round path: its masked uploads are a
host-side protocol and cannot be fused into the program.
"""

from __future__ import annotations

import numpy as np

from repro.core import aggregate as AGG
from repro.core.strategies.base import (Strategy, EpochLog, make_full_step,
                                        np_batches)


class FedAvg(Strategy):
    name = "fl"
    shared_eval_params = True

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._secagg_on = (self.privacy is not None and self.privacy.secagg)
        if self._secagg_on:
            if self.aggregator_spec is not None:
                raise ValueError("aggregator= cannot be combined with "
                                 "privacy.secagg (secure aggregation IS "
                                 "the aggregation rule)")
            if self.participation is not None:
                raise ValueError("participation= with privacy.secagg is "
                                 "not supported (the pairwise-mask "
                                 "protocol assumes a fixed cohort)")
            self._agg = None                 # built in setup (needs SecAgg)
        else:
            self._agg = AGG.make_aggregator(self.aggregator_spec)
            if self.participation is not None and \
                    not self._agg.scan_compatible:
                raise ValueError("participation= needs an aggregator "
                                 "that folds into the round scan "
                                 "(scan_compatible)")

    def setup(self, key):
        params = self.adapter.init(key)
        if not hasattr(self, "_opt"):
            self._opt = self.opt_factory()
            self._step = make_full_step(self.adapter, self._opt,
                                        self.privacy)
        if self._secagg_on and not hasattr(self, "secagg"):
            from repro.privacy.secagg import SecAgg
            self.secagg = SecAgg(self.n_clients, seed=self.privacy.seed)
            self._agg = AGG.SecAggregator(self.secagg)
        return {"params": params}

    def _aggregate(self, locals_, weights, prev=None):
        return self._agg.aggregate_trees(locals_, weights, prev)

    def _round_telemetry(self, tel, losses, metrics, mask, old_gp,
                         stacked_locals, new_gp):
        """Reduce one FL round's ``[C, NB]`` stacks (+ the in-round update
        cosine from the stacked locals) into a RoundTelemetry."""
        from repro.core.strategies import engine as ENG
        from repro.obs import telemetry as T
        extra = None
        if tel.update_cosine:
            cos = np.asarray(ENG.update_cosine(stacked_locals, old_gp,
                                               new_gp))
            extra = {"update_cosine": cos[None]}
        metrics = {k: np.asarray(v)[None] for k, v in metrics.items()}
        return T.rounds_client_major(tel, np.asarray(losses)[None], metrics,
                                     mask, self.n_clients, extra)[0]

    def _run_epoch_stepwise(self, state, client_data, rng, batch_size):
        tel = self._tel
        step = self._step if tel is None else self._get_obs(
            "_step_obs", tel,
            lambda: make_full_step(self.adapter, self._opt, self.privacy,
                                   tel))
        locals_, weights, losses = [], [], []
        loss_w, client_steps, met_vals = [], [], []
        for ci, data in enumerate(client_data):
            p = state["params"]                    # start from global
            opt_state = self._opt.init(p)          # fresh optimizer per round
            n = len(data["label"])
            steps = 0
            for batch in np_batches(data, batch_size, rng,
                                    self.drop_remainder):
                args = ((p, opt_state, batch, self._next_key())
                        if self._keyed else (p, opt_state, batch))
                out = step(*args)
                self._count_dispatch()
                p, opt_state, loss = out[0], out[1], out[2]
                if tel is not None:
                    met_vals.append(out[3])
                losses.append(float(loss))
                loss_w.append(len(batch["label"]))
                steps += 1
                self._dp_account(ci, n, batch_size)
            locals_.append(p)
            weights.append(n)
            client_steps.append(steps)
        old_gp = state["params"]
        state["params"] = self._aggregate(locals_, weights, prev=old_gp)
        log = EpochLog(losses, len(losses), weights=loss_w,
                       client_steps=client_steps)
        if tel is not None:
            from repro.core.partition import stack_trees
            from repro.obs import telemetry as T
            arr, mask = T.pack_client_major(losses, client_steps)
            metrics = {
                k: T.pack_client_major([float(m[k]) for m in met_vals],
                                       client_steps)[0]
                for k in (met_vals[0] if met_vals else {})}
            log.telemetry = self._round_telemetry(
                tel, arr, metrics, mask, old_gp, stack_trees(locals_),
                state["params"])
        return state, log

    def _run_epoch_compiled(self, state, client_data, rng, batch_size):
        """One compiled round whose aggregation runs on the host (secure
        aggregation, or an aggregator that is not ``scan_compatible``)."""
        from repro.core.strategies import engine as ENG
        tel = self._tel
        place = self.placement
        with self._span("pack") as sp:
            packed = ENG.pack_epoch(client_data, batch_size, rng,
                                    self.drop_remainder,
                                    pad_clients=place.n_pad,
                                    span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(packed.batches, packed.mask.size,
                                           sum(packed.n_batches)))
        if packed.nb_max == 0:
            return state, EpochLog([], 0,
                                   client_steps=[0] * self.n_clients)
        if tel is None:
            if not hasattr(self, "_epoch_c"):
                self._epoch_c = ENG.make_fl_epoch(self.adapter, self._opt,
                                                  self.privacy, place)
            epoch_fn = self._epoch_c
        else:
            epoch_fn = self._get_obs(
                "_epoch_obs_c", tel,
                lambda: ENG.make_fl_epoch(self.adapter, self._opt,
                                          self.privacy, place, tel))
        key_idx = place.put(ENG.key_index_grid(self, packed))
        batches = place.put(packed.batches)
        out = self._enqueue(epoch_fn, (
            state["params"], batches, place.put(packed.mask),
            place.put(packed.ex_weights), key_idx,
            self._privacy_base_key()), stash=False)
        locals_stacked, losses = out[0], self._wait(out[1])
        with self._span("account"):
            return state, self._aggregate_epoch(
                state, out, locals_stacked, losses, packed, batch_size, tel)

    def _aggregate_epoch(self, state, out, locals_stacked, losses, packed,
                         batch_size, tel):
        """Host aggregation and accounting of one compiled FL epoch."""
        from repro.core.strategies import engine as ENG
        old_gp = state["params"]
        # the aggregator's host path: the default WeightedMean dispatches
        # the exact pre-refactor jitted weighted mean; SecAggregator
        # unstacks real hospitals and runs the masked-upload protocol
        state["params"] = self._agg.host(
            locals_stacked, np.asarray(packed.n_samples, np.float32),
            prev=old_gp)
        flat, loss_w = ENG.client_major_log(losses, packed)
        for ci, nb in enumerate(packed.n_batches):
            if nb:
                self._dp_account(ci, packed.n_samples[ci], batch_size,
                                 count=nb)
        log = EpochLog(flat, len(flat), weights=loss_w,
                       client_steps=list(
                           packed.n_batches[:self.n_clients]))
        if tel is not None:
            log.telemetry = self._round_telemetry(
                tel, losses, {k: np.asarray(v) for k, v in out[2].items()},
                packed.mask, old_gp, locals_stacked, state["params"])
        return log

    @property
    def _whole_run(self):
        # secagg aggregates host-side per-round (masked uploads) and keeps
        # a compiled round per epoch (``_run_epoch_compiled``); so does
        # any non-scan-compatible custom aggregator
        if self._secagg_on:
            return False
        return self._agg.scan_compatible

    @property
    def _run_aggregator(self):
        """Aggregator passed into the whole-run builders: ``None`` for the
        default weighted mean so the fused program traces byte-identical
        to the pre-aggregator engine."""
        return None if type(self._agg) is AGG.WeightedMean else self._agg

    def _run_compiled(self, state, client_data, rng, batch_size, n_epochs):
        from repro.core.strategies import engine as ENG
        if self.participation is not None:
            return self._run_participation(state, client_data, rng,
                                           batch_size, n_epochs)
        tel = self._tel
        place = self.placement
        with self._span("pack") as sp:
            batches, packed = ENG.pack_run(client_data, batch_size, rng,
                                           n_epochs, self.drop_remainder,
                                           pad_clients=place.n_pad,
                                           span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(batches,
                                           n_epochs * packed.mask.size,
                                           n_epochs * sum(packed.n_batches)))
        if tel is None:
            if not hasattr(self, "_run_c"):
                self._run_c = ENG.make_fl_run(
                    self.adapter, self._opt, self.privacy, place,
                    aggregator=self._run_aggregator)
            run_fn = self._run_c
        else:
            run_fn = self._get_obs(
                "_run_obs_c", tel,
                lambda: ENG.make_fl_run(self.adapter, self._opt,
                                        self.privacy, place, tel,
                                        aggregator=self._run_aggregator))
        key_idx = np.stack([ENG.key_index_grid(self, packed)
                            for _ in range(n_epochs)])
        args = (state["params"], place.put(batches, axis=1),
                place.put(packed.mask), place.put(packed.ex_weights),
                place.put(key_idx, axis=1), self._privacy_base_key(),
                np.asarray(packed.n_samples, np.float32))
        if tel is None:
            state["params"], losses = self._enqueue(run_fn, args)
        else:
            state["params"], (losses, met) = self._enqueue(run_fn, args)
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._span("account"):
            logs = []
            for e in range(n_epochs):
                flat, loss_w = ENG.client_major_log(losses[e], packed)
                logs.append(EpochLog(flat, len(flat), weights=loss_w,
                                     client_steps=list(
                                         packed.n_batches[:self.n_clients])))
            if tel is not None:
                from repro.obs import telemetry as T
                met = {k: np.asarray(v) for k, v in met.items()}
                extra = ({"update_cosine": met.pop("update_cosine")}
                         if "update_cosine" in met else None)
                rounds = T.rounds_client_major(tel, losses, met,
                                               packed.mask, self.n_clients,
                                               extra)
                for log, r in zip(logs, rounds):
                    log.telemetry = r
            for ci, nb in enumerate(packed.n_batches):
                if nb:
                    self._dp_account(ci, packed.n_samples[ci], batch_size,
                                     count=nb * n_epochs)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, batches, packed
        return state, logs

    def _run_participation(self, state, client_data, rng, batch_size,
                           n_epochs):
        """Whole participating run: per-round K-of-N subsampling packed
        into a fixed slot axis, still ONE dispatch.

        The per-step key-index grid is laid out over the VIRTUAL full-N
        run — round r, hospital g, local step t gets the index the
        non-participating run would give it — so a hospital's DP/noise
        draws depend only on (round, hospital) and ``Participation(k=N)``
        reproduces ``participation=None`` exactly."""
        from repro.core.strategies import engine as ENG
        part = self.participation
        tel = self._tel
        with self._span("pack") as sp:
            batches, pack = ENG.pack_participation_run(
                client_data, batch_size, rng, n_epochs, part,
                self.drop_remainder, span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(batches, pack.mask.size,
                                           pack.mask.sum()))
        nbs = pack.n_batches
        T_N = int(sum(nbs))
        prefix = np.concatenate([[0], np.cumsum(nbs)[:-1]]).astype(np.int64)
        key_idx = np.zeros((n_epochs, pack.n_slots, pack.nb_max), np.uint32)
        if self._keyed:
            base0 = self._key_step
            for e in range(n_epochs):
                for s in range(pack.n_slots):
                    g = int(pack.slot_gid[e, s])
                    if g >= 0 and nbs[g]:
                        key_idx[e, s, :nbs[g]] = (
                            base0 + 1 + e * T_N + prefix[g]
                            + np.arange(nbs[g], dtype=np.int64))
            self._key_step += n_epochs * T_N
        if tel is None:
            if not hasattr(self, "_run_part_c"):
                self._run_part_c = ENG.make_fl_run_participation(
                    self.adapter, self._opt, self.privacy,
                    aggregator=self._agg)
            run_fn = self._run_part_c
        else:
            run_fn = self._get_obs(
                "_run_part_obs_c", tel,
                lambda: ENG.make_fl_run_participation(
                    self.adapter, self._opt, self.privacy, tel,
                    aggregator=self._agg))
        args = (state["params"], batches, pack.mask, pack.ex_weights,
                key_idx, self._privacy_base_key(), pack.agg_w,
                pack.staleness, pack.slot_gid)
        met = None
        if tel is None:
            state["params"], losses = self._enqueue(run_fn, args)
        else:
            state["params"], (losses, met) = self._enqueue(run_fn, args)
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(losses)
        with self._span("account"):
            logs = self._account_participation(
                losses, met, pack, part, batch_size, n_epochs, tel)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, batches, pack
        return state, logs

    def _account_participation(self, losses, met, pack, part, batch_size,
                               n_epochs, tel):
        """Epoch logs, telemetry and DP accounting of a participating
        run."""
        nbs = pack.n_batches
        logs = []
        for e in range(n_epochs):
            flat, loss_w = [], []
            csteps = [0] * pack.n_global
            for s in range(pack.n_slots):
                g = int(pack.slot_gid[e, s])
                if g < 0:
                    continue
                flat.extend(float(x) for x in losses[e, s, :nbs[g]])
                loss_w.extend(pack.step_examples[g])
                csteps[g] = nbs[g]
            logs.append(EpochLog(flat, len(flat), weights=loss_w,
                                 client_steps=csteps))
        if tel is not None:
            from repro.obs import telemetry as T
            met = {k: np.asarray(v) for k, v in met.items()}
            extra = ({"update_cosine": met.pop("update_cosine")}
                     if "update_cosine" in met else None)
            rounds = T.rounds_participation(tel, losses, met, pack, extra)
            for log, r in zip(logs, rounds):
                log.telemetry = r
        # RDP accounting: with sampling randomness EVERY hospital composes
        # EVERY round at the amplified rate (q_round * q_batch) over its
        # would-be step count; a deterministic schedule composes only the
        # realized rounds at the plain batch rate
        if part.kind == "schedule":
            for g in range(pack.n_global):
                cnt = int(pack.part_mask[:, g].sum()) * nbs[g]
                if cnt:
                    self._dp_account(g, pack.n_samples[g], batch_size,
                                     count=cnt)
        else:
            self._last_part_nbs = list(nbs)
            for g in range(pack.n_global):
                if nbs[g]:
                    self._dp_account(g, pack.n_samples[g], batch_size,
                                     count=nbs[g] * n_epochs,
                                     q_scale=part.rate)
        return logs

    def params_for_eval(self, state, client_idx):
        return state["params"]
