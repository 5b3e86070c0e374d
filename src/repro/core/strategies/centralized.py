"""Centralized training — the paper's benchmark upper bound (§3.6)."""

from __future__ import annotations

import numpy as np

from repro.core.strategies.base import (Strategy, EpochLog, make_full_step,
                                        np_batches)


def _pool(client_data) -> dict:
    """Every hospital's training rows as one set."""
    return {k: np.concatenate([d[k] for d in client_data])
            for k in client_data[0]}


class Centralized(Strategy):
    name = "centralized"
    shared_eval_params = True
    # the per-round epsilon series composes at the pooled sampling rate:
    # every hospital's records sit in the pooled training set
    _eps_pooled = True

    def setup(self, key):
        params = self.adapter.init(key)
        if not hasattr(self, "_opt"):
            self._opt = self.opt_factory()
            self._step = make_full_step(self.adapter, self._opt,
                                        self.privacy)
        return {"params": params, "opt": self._opt.init(params)}

    def _round_telemetry(self, tel, losses, metrics):
        """Reduce one pooled epoch's per-step taps (the centralized
        trainer is a single pooled 'hospital')."""
        from repro.obs import telemetry as T
        nb = len(losses)
        if nb == 0:
            return T.RoundTelemetry(0, {})
        arr = np.asarray(losses, np.float64)[None, None]
        mets = {k: np.asarray(v, np.float64)[None, None]
                for k, v in metrics.items()}
        return T.rounds_client_major(tel, arr, mets,
                                     np.ones((1, nb), bool), 1)[0]

    def _run_epoch_stepwise(self, state, client_data, rng, batch_size):
        pooled = _pool(client_data)
        tel = self._tel
        step = self._step if tel is None else self._get_obs(
            "_step_obs", tel,
            lambda: make_full_step(self.adapter, self._opt, self.privacy,
                                   tel))
        n_pooled = len(pooled["label"])
        losses, weights, met_vals = [], [], []
        for batch in np_batches(pooled, batch_size, rng,
                                self.drop_remainder):
            args = ((state["params"], state["opt"], batch,
                     self._next_key()) if self._keyed
                    else (state["params"], state["opt"], batch))
            out = step(*args)
            self._count_dispatch()
            state["params"], state["opt"], loss = out[0], out[1], out[2]
            if tel is not None:
                met_vals.append(out[3])
            losses.append(float(loss))
            weights.append(len(batch["label"]))
            # centralized DP: every hospital's records sit in the pooled
            # set, so each carries the same pooled-rate guarantee
            for ci in range(self.n_clients):
                self._dp_account(ci, n_pooled, batch_size)
        log = EpochLog(losses, len(losses), weights=weights)
        if tel is not None:
            log.telemetry = self._round_telemetry(
                tel, losses,
                {k: [float(m[k]) for m in met_vals]
                 for k in (met_vals[0] if met_vals else {})})
        return state, log

    def _empty_run(self, client_data, batch_size):
        # the pooled set is cut into batches, not the hospitals' own
        from repro.core.strategies import engine as ENG
        return ENG.empty_run([sum(len(next(iter(d.values())))
                                  for d in client_data)], batch_size,
                             self.drop_remainder)

    def _empty_epoch(self, state, client_data, rng, batch_size):
        from repro.core.strategies import engine as ENG
        ENG.pack_epoch([_pool(client_data)], batch_size, rng,
                       self.drop_remainder)
        return EpochLog([], 0)

    def _run_compiled(self, state, client_data, rng, batch_size, n_epochs):
        from repro.core.strategies import engine as ENG
        tel = self._tel
        with self._span("pack") as sp:
            pooled = _pool(client_data)
            batches, packed = ENG.pack_run([pooled], batch_size, rng,
                                           n_epochs, self.drop_remainder,
                                           span=self._span)
            if sp is not None:
                sp.set(**ENG.pack_counters(batches,
                                           n_epochs * packed.mask.size,
                                           n_epochs * sum(packed.n_batches)))
        nb = packed.n_batches[0]
        if tel is None:
            if not hasattr(self, "_run_c"):
                self._run_c = ENG.make_seq_run(self.adapter, self._opt,
                                               self.privacy)
            run_fn = self._run_c
        else:
            run_fn = self._get_obs(
                "_run_obs_c", tel,
                lambda: ENG.make_seq_run(self.adapter, self._opt,
                                         self.privacy, tel))
        key_idx = np.zeros((n_epochs, packed.nb_max), np.uint32)
        if self._keyed:
            for e in range(n_epochs):
                key_idx[e, :nb] = self._take_key_indices(nb)
        batches = {k: v[:, 0] for k, v in batches.items()}    # [E, NB, ...]
        ex_w = None if packed.ex_weights is None else packed.ex_weights[0]
        args = (state["params"], state["opt"], batches, packed.mask[0],
                ex_w, key_idx, self._privacy_base_key())
        out = self._enqueue(run_fn, args)
        state["params"], state["opt"] = out[0], out[1]
        self._run_calls = getattr(self, "_run_calls", 0) + 1
        losses = self._wait(out[2])
        with self._span("account"):
            logs = [EpochLog([float(x) for x in losses[e, :nb]], nb,
                             weights=packed.step_examples[0])
                    for e in range(n_epochs)]
            if tel is not None:
                met = {k: np.asarray(v) for k, v in out[3].items()}
                for e, log in enumerate(logs):
                    log.telemetry = self._round_telemetry(
                        tel, [float(x) for x in losses[e, :nb]],
                        {k: v[e, :nb] for k, v in met.items()})
            for ci in range(self.n_clients):
                self._dp_account(ci, packed.n_samples[0], batch_size,
                                 count=nb * n_epochs)
            # the run's host batches and donated inputs are freed here,
            # inside "account", not in the frame's teardown after it
            del args, batches, packed, pooled, out
        return state, logs

    def params_for_eval(self, state, client_idx):
        return state["params"]
