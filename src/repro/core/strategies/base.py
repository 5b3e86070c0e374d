"""Strategy API + shared step builders.

Every strategy consumes a ``SplitAdapter`` (architecture-agnostic) and an
optimizer factory, and exposes:

    setup(key)                        -> state
    run_epoch(state, client_data, rng, batch_size) -> (state, log)
    scores(state, client_idx, data, batch_size)    -> per-sample scores

``client_data`` is a list (len n_clients) of dicts of numpy arrays.
Evaluation follows the paper (§3.4): a sample from hospital i always passes
through hospital i's own client segment(s); FL/centralized have one model.

Two execution engines share the SAME pure step functions (``full_step_fn``
/ ``split_step_fn`` / ``sflv3_step_fn``):

  * ``compiled`` (the DEFAULT; repro.core.strategies.engine): a whole
    multi-epoch ``Strategy.run`` lowered to ONE XLA program — ``lax.scan``
    over rounds and batches, ``vmap`` over the hospital axis where
    semantics allow; ``run_epoch`` is a one-round run.
  * ``stepwise`` (legacy; kept as the parity oracle): a Python host loop
    dispatching one jitted step per mini-batch.

Because both engines trace the identical step math, they agree to float32
round-off (asserted at 1e-5 in tests/test_engine.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Callable

import jax
import numpy as np

from repro.core.partition import SplitAdapter, stack_trees, unstack_tree
from repro import optim as O
# importing repro.obs installs its compile log before any program traces
from repro.obs import compile_log


@dataclasses.dataclass
class EpochLog:
    """Per-epoch training log.

    ``weights`` are per-step valid-example counts (None == every step saw
    a full batch); ``mean_loss`` is the example-weighted mean so a compiled
    (pad-and-mask) epoch and a stepwise epoch over the same data report
    identical statistics.  ``client_steps`` counts optimizer steps actually
    attributed to each hospital (masked padding steps excluded).
    """
    losses: list
    steps: int
    weights: list | None = None
    client_steps: list[int] | None = None
    # repro.obs.telemetry.RoundTelemetry when the epoch ran observed
    # (typed loosely so the strategy layer never hard-imports repro.obs)
    telemetry: object = None

    @property
    def mean_loss(self):
        if not self.losses:
            return float("nan")
        if self.weights is None:
            return float(np.mean(self.losses))
        w = np.asarray(self.weights, dtype=np.float64)
        l = np.asarray(self.losses, dtype=np.float64)
        return float((l * w).sum() / max(w.sum(), 1.0))


def _run_images(logs, batch_size: int) -> int:
    """Training examples with nonzero weight over a run's epoch logs."""
    n = 0
    for log in logs:
        if log.weights is not None:
            n += int(np.sum(log.weights))
        elif log.client_steps is not None:
            n += int(sum(log.client_steps)) * batch_size
        else:
            n += int(log.steps) * batch_size
    return n


def _input_bytes(args) -> dict:
    """The ``enqueue`` span's counters: bytes of every input array, and
    of those still on the host (copied to the device by the call)."""
    total = host = 0
    for leaf in jax.tree.leaves(args):
        if isinstance(leaf, np.ndarray):
            host += leaf.nbytes
            total += leaf.nbytes
        elif isinstance(leaf, jax.Array) and not jax.dtypes.issubdtype(
                leaf.dtype, jax.dtypes.prng_key):
            total += leaf.nbytes
    return {"bytes_in": int(total), "bytes_host": int(host)}


# aggregation cores live in repro.core.aggregate (PR 9); re-exported here
# because the strategy layer and external callers import them from base
from repro.core.aggregate import tree_mean, tree_weighted_mean  # noqa: E402


def np_batches(data: dict, batch_size: int, rng: np.random.Generator | None,
               drop_remainder: bool = True):
    """Shuffle + slice a client's epoch into batch dicts.

    ``drop_remainder=True`` reproduces the paper testbed (and this repo's
    historical behaviour): the final ``n % batch_size`` samples are silently
    dropped.  ``drop_remainder=False`` keeps them as one short final batch —
    the stepwise counterpart of the compiled engine's pad-and-mask rows.
    """
    n = len(next(iter(data.values())))
    idx = np.arange(n)
    if rng is not None:
        rng.shuffle(idx)
    stop = (n // batch_size) * batch_size if drop_remainder else n
    return [{k: v[idx[s:s + batch_size]] for k, v in data.items()}
            for s in range(0, stop, batch_size)]


class Strategy:
    name: str = "base"
    # every hospital scores with the same params (FL/centralized): the
    # batched scorer keeps ONE param copy instead of an n_clients stack
    shared_eval_params: bool = False

    # centralized overrides: epsilon series composes at the pooled rate
    _eps_pooled: bool = False

    def __init__(self, adapter: SplitAdapter, opt_factory: Callable[[], O.Optimizer],
                 n_clients: int, privacy=None, engine: str = "compiled",
                 drop_remainder: bool = True, shard: bool = False,
                 observe=None, participation=None, aggregator=None):
        if engine not in ("stepwise", "compiled"):
            raise ValueError(f"unknown engine {engine!r}")
        self.adapter = adapter
        self.opt_factory = opt_factory
        self.n_clients = n_clients
        self.privacy = privacy          # repro.privacy.PrivacyConfig | None
        self.engine = engine
        self.drop_remainder = drop_remainder
        self.shard = shard              # place hospital axis across devices
        from repro.core.participation import as_participation
        self.participation = as_participation(participation)
        if self.participation is not None:
            if self.participation.n_global != n_clients:
                raise ValueError(
                    f"participation.n_global={self.participation.n_global} "
                    f"!= n_clients={n_clients}")
            if engine != "compiled":
                raise ValueError(
                    "participation= requires the compiled engine (the "
                    "stepwise oracle has no slot-packed hospital axis)")
            if shard:
                raise ValueError("participation= with shard= is not "
                                 "supported (slot axis vs mesh padding)")
        # aggregator spec (repro.core.aggregate); resolved by FedAvg —
        # make_strategy rejects it for every other method
        self.aggregator_spec = aggregator
        from repro.core.placement import Placement
        # pad-to-mesh hospital-axis placement (no-op mesh on one device;
        # the stepwise parity oracle never pads or shards)
        self.placement = Placement.make(
            n_clients, enabled=shard and engine == "compiled")
        self._accountants = None
        self._key_step = 0
        # observability (repro.obs): metric-tap spec, span tracer, and the
        # training-program dispatch counter — all inert when unused
        from repro.obs.telemetry import as_telemetry
        self.observe = as_telemetry(observe)
        self._tel_active = self.observe
        self._tracer = None
        self._dispatches = 0
        self.last_run_telemetry = None

    # -- to implement ---------------------------------------------------------
    def setup(self, key):
        raise NotImplementedError

    def _run_epoch_stepwise(self, state, client_data, rng, batch_size):
        """One epoch as a host loop of per-batch jitted steps: the parity
        oracle the compiled programs are tested against."""
        raise NotImplementedError

    def params_for_eval(self, state, client_idx) -> dict:
        """Full param dict (all segments) used to score client ``client_idx``."""
        raise NotImplementedError

    # -- deployment (repro.serving) -------------------------------------------
    def export(self, state, client_idx: int = 0, meta: dict | None = None):
        """Materialize the deployable full model as a ``ServableModel``.

        FL/centralized export the one global tree (``client_idx`` is
        moot); the split family exports hospital ``client_idx``'s client
        segment(s) stitched with the shared server segment at the cut —
        the exact composition ``params_for_eval`` scores with, so the
        export's scores are bit-identical to this strategy's eval
        (``ServableModel.scores`` replays the same compiled program).
        Round-trip through ``repro.serving.export.save_servable``.
        """
        from repro.serving.export import ServableModel
        params = jax.tree.map(np.asarray,
                              self.params_for_eval(state, client_idx))
        m = {"strategy": self.name, "client_idx": int(client_idx),
             "n_clients": self.n_clients, **(meta or {})}
        return ServableModel(adapter=self.adapter, params=params,
                             shared=self.shared_eval_params, meta=m)

    # -- training ---------------------------------------------------------------
    def run_epoch(self, state, client_data, rng, batch_size):
        """Train one epoch (one FL round); returns ``(state, EpochLog)``.

        Under the compiled engine this is ``run(n_epochs=1)`` without the
        ``run`` span: the same one-round whole-run program, the same
        shuffles and PRNG counter, so the state and log match it bit for
        bit.  A participating strategy refuses it: each call would be
        round 0 again, the same cohort drawn every time while its privacy
        is accounted at the sampled rate; ``run`` numbers its rounds.
        """
        if self.participation is not None:
            raise ValueError(
                "run_epoch with participation= would redraw round 0's "
                "cohort at every call; use run(n_epochs=...), which "
                "samples a cohort per round")
        if self.engine == "stepwise":
            return self._run_epoch_stepwise(state, client_data, rng,
                                            batch_size)
        if not self._whole_run:
            return self._run_epoch_compiled(state, client_data, rng,
                                            batch_size)
        state, (log,) = self._run(state, client_data, rng, batch_size, 1)
        return state, log

    @property
    def _whole_run(self) -> bool:
        """The compiled engine lowers a multi-epoch run into ONE program;
        only FedAvg's host-side aggregations say otherwise."""
        return True

    def _run_compiled(self, state, client_data, rng, batch_size, n_epochs):
        """The whole run as one program, for a run that has a batch."""
        raise NotImplementedError

    def _run_epoch_compiled(self, state, client_data, rng, batch_size):
        """One compiled round for a strategy without a whole run."""
        raise NotImplementedError

    def _empty_run(self, client_data, batch_size) -> bool:
        """No hospital's training set yields a single batch."""
        from repro.core.strategies import engine as ENG
        return ENG.empty_run([len(next(iter(d.values())))
                              for d in client_data], batch_size,
                             self.drop_remainder)

    def _empty_epoch(self, state, client_data, rng, batch_size):
        """One epoch of a run without a batch: the shuffles a pack of it
        would draw from ``rng``, and an empty log."""
        from repro.core.strategies import engine as ENG
        ENG.pack_epoch(client_data, batch_size, rng, self.drop_remainder)
        return EpochLog([], 0, client_steps=[0] * self.n_clients)

    def run(self, state, client_data, rng, batch_size, n_epochs,
            observe=None):
        """Train ``n_epochs`` epochs/rounds; returns ``(state, logs)`` with
        one ``EpochLog`` per epoch.

        Under the compiled engine the WHOLE run lowers into a single XLA
        program — an outer scan over rounds wrapping the epoch body, with
        the FedAvg aggregation / SFLv2 client averaging folded in — so one
        host dispatch executes every epoch.  Strategies that cannot fold
        their round boundary in-graph (secure aggregation's host-side
        masked uploads) and the stepwise engine loop over ``run_epoch``;
        both orders consume ``rng`` and the PRNG step counter
        identically, so results match the fused path to float round-off.

        ``observe`` (repro.obs.Telemetry | True | False | None) overrides
        the constructor's telemetry spec for this run: the metric taps
        ride the scans as extra outputs — the whole run stays ONE dispatch
        and params are bit-identical to an unobserved run — and the
        reduced per-round telemetry lands on each ``EpochLog.telemetry``
        plus ``self.last_run_telemetry``.  ``None`` inherits the
        constructor setting; ``False`` disables for this run.
        """
        if n_epochs <= 0:
            return state, []
        from repro.obs.telemetry import as_telemetry
        if observe is None:
            tel = self.observe
        else:
            tel = None if observe is False else as_telemetry(observe)
        prev = self._tel_active
        self._tel_active = tel
        try:
            with self._span("run", strategy=self.name,
                            n_epochs=n_epochs) as sp:
                state, logs = self._run(state, client_data, rng,
                                        batch_size, n_epochs)
                logs = self._finish_run(client_data, batch_size, logs)
                if sp is not None:
                    sp.set(images=_run_images(logs, batch_size))
                return state, logs
        finally:
            self._tel_active = prev

    def _run(self, state, client_data, rng, batch_size, n_epochs):
        if self.engine == "stepwise" or not self._whole_run:
            logs = []
            for i in range(n_epochs):
                with self._span(f"round {i}"):
                    state, log = self.run_epoch(state, client_data, rng,
                                                batch_size)
                logs.append(log)
            return state, logs
        if self._empty_run(client_data, batch_size):
            # nothing to compile: a degenerate participating run trains
            # nothing, any other logs one empty epoch per epoch
            if self.participation is not None:
                return state, []
            return state, [self._empty_epoch(state, client_data, rng,
                                             batch_size)
                           for _ in range(n_epochs)]
        return self._run_compiled(state, client_data, rng, batch_size,
                                  n_epochs)

    def _finish_run(self, client_data, batch_size, logs):
        """Assemble ``last_run_telemetry`` (one RoundTelemetry per epoch
        plus the per-round cumulative RDP epsilon series) from the logs an
        observed run produced."""
        tel = self._tel_active
        if tel is None:
            self.last_run_telemetry = None
            return logs
        from repro.obs import telemetry as T
        rounds = []
        for i, log in enumerate(logs):
            r = log.telemetry
            if r is None:
                r = T.RoundTelemetry(i, {})
                log.telemetry = r
            r.round_index = i
            rounds.append(r)
        if tel.epsilon and self._dp:
            ns = [len(d["label"]) for d in client_data]
            kw = {}
            part = self.participation
            if part is not None and part.kind != "schedule":
                # amplification: every hospital composes every round at
                # the amplified rate over its would-be step count (the
                # realized zeros of unsampled rounds don't apply here);
                # deterministic schedules keep the realized client_steps
                kw = dict(q_scale=part.rate,
                          steps_override=getattr(self, "_last_part_nbs",
                                                 None))
            eps = T.epsilon_rounds(self.privacy, logs, ns, batch_size,
                                   pooled=self._eps_pooled, **kw)
            if eps is not None:
                for r, e in zip(rounds, eps):
                    r.epsilon = e
        self.last_run_telemetry = T.RunTelemetry(self.name, self.n_clients,
                                                 rounds)
        return logs

    # -- observability plumbing (repro.obs) -----------------------------------
    @property
    def _tel(self):
        """The active Telemetry spec (run() override or the constructor's)."""
        return self._tel_active

    def attach_tracer(self, tracer):
        """Attach a ``repro.obs.trace.Tracer`` (``None`` detaches): each
        run records ``run -> pack{gather[, stack]} -> enqueue -> wait ->
        account`` spans with their counters, and every compile while it
        is attached lands as a ``compile.*`` span."""
        if self._tracer is not None:
            compile_log.detach(self._tracer)
        self._tracer = tracer
        if tracer is not None:
            compile_log.attach(tracer)
        return tracer

    def _span(self, name, **args):
        """The tracer's span (yields a ``Span`` for counters), or a no-op
        context yielding None when no tracer is attached."""
        if self._tracer is None:
            return contextlib.nullcontext()
        return self._tracer.span(name, **args)

    def _enqueue(self, fn, args, stash: bool = True):
        """Call a compiled program under the ``enqueue`` span: the jitted
        call, which copies host inputs to the device and returns once the
        program is queued.  ``stash`` keeps ``(fn, abstract args)`` as
        ``_last_run_invocation`` (``obs.profile.hlo_cost``) and on the
        span, so the program's device operations can be named."""
        with self._span("enqueue") as sp:
            if sp is not None:
                sp.set(program=getattr(fn, "__name__", "program"),
                       **_input_bytes(args))
            out = fn(*args)
            if stash:
                from repro.core.strategies.engine import abstract_args
                self._last_run_invocation = (fn, abstract_args(args))
                if sp is not None:
                    sp.program = self._last_run_invocation
        self._count_dispatch()
        return out

    def _wait(self, losses) -> np.ndarray:
        """The run's losses on the host, under the ``wait`` span: the host
        is blocked until the program has finished."""
        with self._span("wait"):
            return np.asarray(losses)

    def _count_dispatch(self, n: int = 1):
        """Tally one host->device training-program invocation (a compiled
        epoch/run call or a stepwise per-batch step)."""
        self._dispatches += n

    def _get_obs(self, attr, tel, build):
        """Cache an observed (telemetry-variant) compiled program under
        ``attr``, keyed on the Telemetry spec — separate from the
        unobserved caches so enabling telemetry never evicts them."""
        cache = getattr(self, attr, None)
        if cache is None or cache[0] != tel:
            cache = (tel, build())
            setattr(self, attr, cache)
        return cache[1]

    # -- privacy plumbing -----------------------------------------------------
    @property
    def _dp(self) -> bool:
        """DP-SGD (clip/noise on gradients) active."""
        return self.privacy is not None and self.privacy.dp_enabled

    @property
    def _keyed(self) -> bool:
        """Jitted step consumes a PRNG key (DP-SGD or cut-layer noise)."""
        p = self.privacy
        return p is not None and (p.dp_enabled or p.cut_noise_std > 0)

    def _privacy_base_key(self):
        if not hasattr(self, "_base_key"):
            seed = self.privacy.seed if self.privacy is not None else 0
            self._base_key = jax.random.key(seed)
        return self._base_key

    def _next_key(self):
        """Fresh per-step key derived from the privacy seed."""
        from repro.privacy.dpsgd import step_key
        self._key_step += 1
        return step_key(self._privacy_base_key(), np.uint32(self._key_step))

    def _take_key_indices(self, count: int) -> np.ndarray:
        """Reserve ``count`` sequential step-key indices.

        The compiled engine derives step keys INSIDE the scan as
        ``fold_in(base_key, index)`` — reserving the same running counter the
        stepwise path consumes keeps the two paths' noise draws identical.
        """
        start = self._key_step
        self._key_step += count
        return np.arange(start + 1, start + count + 1, dtype=np.uint32)

    def _dp_account(self, client_idx, n_samples, batch_size, count=1,
                    q_scale=1.0):
        """Record ``count`` DP mechanism applications on hospital
        ``client_idx``'s data (sampling rate batch_size / n_samples).

        ``q_scale`` composes per-round client subsampling with the batch
        rate: under ``Participation`` a hospital only contributes a round
        with probability K/N (or q), so each round's mechanisms apply to
        any one example with probability ``q_round * q_batch`` — the
        amplified rate the subsampled-Gaussian RDP bound composes at.
        """
        if not self._dp:
            return
        if self._accountants is None:
            from repro.privacy.accountant import RDPAccountant
            self._accountants = [
                RDPAccountant(self.privacy.noise_multiplier,
                              self.privacy.delta)
                for _ in range(self.n_clients)]
        q = min(batch_size / max(n_samples, 1), 1.0) * q_scale
        self._accountants[client_idx].step(q, count)

    def privacy_report(self) -> list:
        """Per-hospital accountant summaries ((eps, delta) each)."""
        if self._accountants is None:
            return []
        return [a.summary() for a in self._accountants]

    # -- common ---------------------------------------------------------------
    def _scores_all_fn(self, placed: bool = False):
        """Jitted (vmap over hospitals) x (vmap over batches) scorer: ONE
        dispatch evaluates every hospital's padded epoch.  ``placed`` runs
        the hospital vmap inside ``shard_map`` chunks on the "hosp" mesh
        (the SPMD partitioner cannot split vmapped convs, so multi-device
        eval chunks explicitly, like the training engine)."""
        fs = self.adapter.full_scores
        in_p = None if self.shared_eval_params else 0
        vmapped = jax.vmap(lambda p, d: jax.vmap(partial(fs, p))(d),
                           in_axes=(in_p, 0))
        if not placed:
            if not hasattr(self, "_scores_all_jit"):
                self._scores_all_jit = jax.jit(vmapped)
            return self._scores_all_jit
        if not hasattr(self, "_scores_all_place_jit"):
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P
            self._scores_all_place_jit = jax.jit(shard_map(
                vmapped, mesh=self.placement.mesh,
                in_specs=(P() if self.shared_eval_params else P("hosp"),
                          P("hosp")),
                out_specs=P("hosp"), check_rep=False))
        return self._scores_all_place_jit

    def _stacked_eval_params(self, state):
        if self.shared_eval_params:
            return self.params_for_eval(state, 0)
        return stack_trees([self.params_for_eval(state, i)
                            for i in range(self.n_clients)])

    def _dispatch_scores(self, params, stacked, placed=False,
                         chunk_batches=None, place=None):
        """Run the vmapped scorer over a ``[C, nb, bs, ...]`` data stack,
        optionally chunking the batch axis so an epoch larger than one
        device batch never materializes as a single device buffer.

        Chunks are fixed-shape ``[C, chunk, bs, ...]`` slices (the last
        chunk pads by repeating its final batch slice, scores sliced
        off), so the chunked path compiles ONE extra program total and
        its per-example math is the same vmapped scorer — parity with
        the unchunked dispatch is tested at <=1e-5.  Returns
        ``[C, nb * bs, ...]``.
        """
        fn = self._scores_all_fn(placed)
        nb = next(iter(stacked.values())).shape[1]
        put = (place.put if placed and place is not None
               else (lambda t: t))
        if chunk_batches is None or int(chunk_batches) >= nb:
            out = np.asarray(fn(params, put(stacked)))
            return out.reshape(out.shape[0], -1, *out.shape[3:])
        ch = int(chunk_batches)
        if ch < 1:
            raise ValueError("chunk_batches must be >= 1")
        outs = []
        for s in range(0, nb, ch):
            sl = {k: v[:, s:s + ch] for k, v in stacked.items()}
            m = min(ch, nb - s)
            if m < ch:
                sl = {k: np.concatenate(
                    [v, np.repeat(v[:, -1:], ch - m, axis=1)], axis=1)
                    for k, v in sl.items()}
            o = np.asarray(fn(params, put(sl)))
            outs.append(o[:, :m])
        out = np.concatenate(outs, axis=1)
        return out.reshape(out.shape[0], -1, *out.shape[3:])

    def scores_all(self, state, datas: list, batch_size=60,
                   chunk_batches=None):
        """Per-sample scores for every hospital in a single jitted dispatch.

        Each hospital's split is padded (repeating the last row — the
        existing partial-batch idiom) to a common ``nb * bs`` grid, stacked
        along a leading hospital axis, and scored by the vmapped scorer;
        padding rows are sliced off per hospital.  With placement enabled
        the hospital axis of the data stack (and the stacked params) is
        padded to the mesh multiple and placed on the "hosp" mesh —
        phantom-row scores are computed and discarded.

        ``chunk_batches`` caps how many padded batches one dispatch
        scores: an epoch bigger than one device batch streams through
        fixed-shape ``[C, chunk_batches, bs, ...]`` slices instead of
        materializing the whole grid on device (parity <=1e-5 with the
        unchunked path; ``None`` keeps the single dispatch).
        """
        ns = [len(d["label"]) for d in datas]
        n_max = max(ns, default=0)
        if n_max == 0:
            return [np.zeros((0,)) for _ in datas]
        bs = min(batch_size, n_max)
        nb = -(-n_max // bs)
        L = nb * bs

        def pad(v):
            if len(v) == 0:                      # empty hospital: all padding
                return np.zeros((L, *v.shape[1:]), v.dtype)
            if len(v) == L:
                return v
            return np.concatenate([v, np.repeat(v[-1:], L - len(v), axis=0)])

        stacked = {k: np.stack([pad(d[k]) for d in datas])
                   for k in datas[0]}
        stacked = {k: v.reshape(len(datas), nb, bs, *v.shape[2:])
                   for k, v in stacked.items()}
        params = self._stacked_eval_params(state)
        place = self.placement
        placed = place.enabled and len(datas) == self.n_clients
        if placed:
            stacked = {k: place.pad_rows(v) for k, v in stacked.items()}
            if not self.shared_eval_params:
                params = place.put(place.pad_tree(params))
        out = self._dispatch_scores(params, stacked, placed=placed,
                                    chunk_batches=chunk_batches,
                                    place=place)
        return [out[i, :ns[i]] for i in range(len(datas))]

    def scores(self, state, client_idx, data, batch_size=60,
               chunk_batches=None):
        """Per-sample scores for EVERY sample of one hospital (the final
        partial batch is padded and sliced, so small hospitals never lose
        eval samples).  Routed through the same vmapped scorer as
        ``scores_all`` with a singleton hospital axis; ``chunk_batches``
        streams large datasets through fixed-shape slices exactly as in
        ``scores_all``."""
        n = len(data["label"])
        if n == 0:
            return np.zeros((0,))
        params = self.params_for_eval(state, client_idx)
        if not self.shared_eval_params:
            params = stack_trees([params])
        bs = min(batch_size, n)
        nb = -(-n // bs)
        L = nb * bs
        stacked = {}
        for k, v in data.items():
            if len(v) != L:
                v = np.concatenate([v, np.repeat(v[-1:], L - len(v), axis=0)])
            stacked[k] = v.reshape(1, nb, bs, *v.shape[1:])
        out = self._dispatch_scores(params, stacked,
                                    chunk_batches=chunk_batches)
        return out[0][:n]

    def evaluate(self, state, clients, split="test", batch_size=60):
        """Pooled metrics across clients, each scored by its own front —
        all hospitals evaluated in one dispatch via ``scores_all``."""
        from repro.train import metrics as MET
        datas = [getattr(c, split) for c in clients]
        scores = self.scores_all(state, datas, batch_size)
        all_labels = [d["label"][:len(s)] for d, s in zip(datas, scores)]
        return MET.all_metrics(np.concatenate(all_labels),
                               np.concatenate(scores))

    def val_loss(self, state, clients, batch_size=60):
        if not hasattr(self, "_val_loss_jit"):
            self._val_loss_jit = jax.jit(partial(self.adapter.full_loss,
                                                 train=False))
        fn = self._val_loss_jit
        tot, n = 0.0, 0
        for i, c in enumerate(clients):
            params = self.params_for_eval(state, i)
            for b in np_batches(c.val, min(batch_size, len(c.val["label"])),
                                None):
                tot += float(fn(params, b)); n += 1
        return tot / max(n, 1)


# ---------------------------------------------------------------------------
# pure step functions — shared verbatim by the stepwise jit wrappers below
# and the compiled engine's scan bodies (repro.core.strategies.engine)
# ---------------------------------------------------------------------------

def _apply_update(opt: O.Optimizer, grads, opt_state, params):
    """One optimizer step under the ``update`` scope (``repro.obs.scopes``):
    returns ``(new params, new optimizer state, updates)``."""
    with jax.named_scope("update"):
        updates, opt_state = opt.update(grads, opt_state, params)
        return O.apply_updates(params, updates), opt_state, updates


def full_step_fn(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                 telemetry=None):
    """Pure step over ALL segments jointly (centralized / FL local).

    Returns ``(step, keyed)`` with
    ``step(params, opt_state, batch, key=None, weights=None)``; ``key`` is
    consumed only when ``keyed`` (DP-SGD), ``weights`` are per-example
    pad-mask weights (None == plain batch mean; unsupported under DP).

    With a ``telemetry`` spec (repro.obs.Telemetry) the step returns one
    extra trailing dict of float32 scalar metric taps (static key set from
    ``telemetry.step_keys``) computed from intermediates the step already
    has — no extra PRNG draws, no reordered math, so params stay
    bit-identical to the unobserved step.
    """
    dp = privacy is not None and privacy.dp_enabled
    if dp:
        from repro.privacy.dpsgd import dp_value_and_grad, keyed

        if telemetry is not None:
            from repro.obs import telemetry as T
            keys = telemetry.step_keys(dp=True, cut=False)
            vg = dp_value_and_grad(keyed(adapter.full_loss), privacy,
                                   with_norms="clip_frac" in keys)

            def dp_step_obs(params, opt_state, batch, key=None,
                            weights=None):
                out = vg(params, batch, key, weights)
                loss, grads = out[0], out[1]
                params, opt_state, updates = _apply_update(
                    opt, grads, opt_state, params)
                met = {}
                if "grad_norm" in keys:
                    met["grad_norm"] = T.global_norm(grads)
                    met["update_norm"] = T.global_norm(updates)
                if "clip_frac" in keys:
                    met["clip_frac"] = T.clip_fraction(
                        out[2]["norms"], privacy.clip_norm, weights)
                return params, opt_state, loss, met
            return dp_step_obs, True

        vg = dp_value_and_grad(keyed(adapter.full_loss), privacy)

        def dp_step(params, opt_state, batch, key=None, weights=None):
            # weights (0/1 pad mask) ride through the DP estimator itself:
            # zero-weight padded examples clip to zero contribution and the
            # 1/B mean divides by the REAL example count
            loss, grads = vg(params, batch, key, weights)
            params, opt_state, _ = _apply_update(opt, grads, opt_state,
                                                 params)
            return params, opt_state, loss
        return dp_step, True

    if telemetry is not None:
        from repro.obs import telemetry as T
        keys = telemetry.step_keys(dp=False, cut=False)

        def step_obs(params, opt_state, batch, key=None, weights=None):
            loss, grads = jax.value_and_grad(
                lambda p: adapter.full_loss(p, batch,
                                            weights=weights))(params)
            params, opt_state, updates = _apply_update(opt, grads,
                                                       opt_state, params)
            met = {}
            if "grad_norm" in keys:
                met["grad_norm"] = T.global_norm(grads)
                met["update_norm"] = T.global_norm(updates)
            return params, opt_state, loss, met
        return step_obs, False

    def step(params, opt_state, batch, key=None, weights=None):
        loss, grads = jax.value_and_grad(
            lambda p: adapter.full_loss(p, batch, weights=weights))(params)
        params, opt_state, _ = _apply_update(opt, grads, opt_state, params)
        return params, opt_state, loss
    return step, False


def split_step_fn(adapter: SplitAdapter, opt_client: O.Optimizer,
                  opt_server: O.Optimizer, transport=None, privacy=None,
                  telemetry=None):
    """Pure SL/SFLv2 step: joint grad through client_i(+tail_i) and server.

    Numerically identical to the paper's two-hop backprop; the hop itself is
    the activation/gradient transfer accounted in repro.core.comm.  With a
    ``transport`` (repro.wire), the cut-layer activations are roundtripped
    through its codec in-graph — the server trains on what crossed the wire.

    Returns ``(step, keyed)`` with ``step(client_params, server_params,
    c_opt, s_opt, batch, key=None, weights=None)``.  A privacy config makes
    the step keyed: DP-SGD clips/noises the JOINT (client, server)
    per-example gradient, and/or Gaussian cut-layer noise rides on the
    boundary after the codec.  Cut-layer noise draws are per-example
    (``repro.privacy.dpsgd.cut_noise_boundary``), so a pad-and-mask padded
    remainder batch (``weights``) noises its real rows exactly as the
    stepwise short batch — padded rows get zero noise and zero loss weight.

    With a ``telemetry`` spec the step returns one extra trailing metric
    dict; cut-layer payload stats observe the FIRST boundary crossing
    (front->middle — the cut) exactly as it ships: post-codec, post-noise.
    Observation never draws keys or reorders the update math.
    """
    nls = adapter.nls
    base_boundary = transport.boundary if transport is not None else None
    # fusable codec: roundtrip+cut-noise as one kernel (bit-equal) when the
    # transport allows it — boundary_with_key does the dispatch
    fuse_codec = transport.fused_codec if transport is not None else None
    priv = (privacy if privacy is not None and
            (privacy.dp_enabled or privacy.cut_noise_std > 0) else None)
    if telemetry is not None:
        from repro.obs import telemetry as T
        keys = telemetry.step_keys(
            dp=priv is not None and priv.dp_enabled, cut=True)
        want_cut = "cut_mean" in keys
        want_clip = "clip_frac" in keys
        want_norms = "grad_norm" in keys

    if priv is not None:
        from repro.privacy.dpsgd import boundary_with_key, dp_value_and_grad

        if telemetry is not None:
            def dp_step_obs(client_params, server_params, c_opt, s_opt,
                            batch, key=None, weights=None):
                both0 = {"c": client_params, "s": server_params}
                met = {}
                if priv.dp_enabled:
                    def loss_fn(both, b, k):
                        params = {"front": both["c"]["front"],
                                  "middle": both["s"]}
                        if nls:
                            params["tail"] = both["c"]["tail"]
                        sink = []
                        bnd = boundary_with_key(base_boundary, priv, k,
                                                codec=fuse_codec)
                        if want_cut:
                            bnd = T.observing_boundary(bnd, sink)
                        loss = adapter.full_loss(params, b, boundary=bnd)
                        if want_cut:
                            return loss, T.payload_moments(sink[0])
                        return loss

                    out = dp_value_and_grad(
                        loss_fn, priv, has_aux=want_cut,
                        with_norms=want_clip)(both0, batch, key, weights)
                    loss, g = out[0], out[1]
                    extras = out[2] if (want_cut or want_clip) else {}
                    if want_cut:
                        met.update(T.moments_to_stats(
                            *T.combine_moments(*extras["aux"], weights)))
                    if want_clip:
                        met["clip_frac"] = T.clip_fraction(
                            extras["norms"], priv.clip_norm, weights)
                else:
                    def loss_fn(both, b, k):
                        params = {"front": both["c"]["front"],
                                  "middle": both["s"]}
                        if nls:
                            params["tail"] = both["c"]["tail"]
                        sink = []
                        bnd = boundary_with_key(base_boundary, priv, k,
                                                weights, codec=fuse_codec)
                        if want_cut:
                            bnd = T.observing_boundary(bnd, sink)
                        loss = adapter.full_loss(params, b, boundary=bnd,
                                                 weights=weights)
                        if want_cut:
                            return loss, T.payload_moments(sink[0],
                                                           weights)
                        return loss

                    if want_cut:
                        (loss, mom), g = jax.value_and_grad(
                            loss_fn, has_aux=True)(both0, batch, key)
                        met.update(T.moments_to_stats(*mom))
                    else:
                        loss, g = jax.value_and_grad(loss_fn)(both0, batch,
                                                              key)
                client_params, c_opt, cu = _apply_update(
                    opt_client, g["c"], c_opt, client_params)
                server_params, s_opt, su = _apply_update(
                    opt_server, g["s"], s_opt, server_params)
                if want_norms:
                    met["grad_norm"] = T.global_norm(g)
                    met["update_norm"] = T.global_norm((cu, su))
                return (client_params, server_params, c_opt, s_opt, loss,
                        met)
            return dp_step_obs, True

        def dp_step(client_params, server_params, c_opt, s_opt, batch,
                    key=None, weights=None):
            def loss_fn(both, b, k):
                params = {"front": both["c"]["front"], "middle": both["s"]}
                if nls:
                    params["tail"] = both["c"]["tail"]
                # under DP the estimator itself weights the clipped
                # per-example grads, so the inner loss stays per-example
                return adapter.full_loss(
                    params, b,
                    boundary=boundary_with_key(
                        base_boundary, priv, k,
                        None if priv.dp_enabled else weights,
                        codec=fuse_codec),
                    weights=None if priv.dp_enabled else weights)

            if priv.dp_enabled:
                loss, g = dp_value_and_grad(loss_fn, priv)(
                    {"c": client_params, "s": server_params}, batch, key,
                    weights)
            else:
                loss, g = jax.value_and_grad(loss_fn)(
                    {"c": client_params, "s": server_params}, batch, key)
            client_params, c_opt, _ = _apply_update(opt_client, g["c"],
                                                    c_opt, client_params)
            server_params, s_opt, _ = _apply_update(opt_server, g["s"],
                                                    s_opt, server_params)
            return client_params, server_params, c_opt, s_opt, loss
        return dp_step, True

    if telemetry is not None:
        def step_obs(client_params, server_params, c_opt, s_opt, batch,
                     key=None, weights=None):
            def loss_fn(cp, sp):
                params = {"front": cp["front"], "middle": sp}
                if nls:
                    params["tail"] = cp["tail"]
                sink = []
                bnd = (T.observing_boundary(base_boundary, sink)
                       if want_cut else base_boundary)
                loss = adapter.full_loss(params, batch, boundary=bnd,
                                         weights=weights)
                if want_cut:
                    return loss, T.payload_moments(sink[0], weights)
                return loss

            if want_cut:
                (loss, mom), (gc, gs) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(client_params,
                                                           server_params)
            else:
                loss, (gc, gs) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1))(client_params, server_params)
            client_params, c_opt, cu = _apply_update(opt_client, gc, c_opt,
                                                      client_params)
            server_params, s_opt, su = _apply_update(opt_server, gs, s_opt,
                                                     server_params)
            met = {}
            if want_cut:
                met.update(T.moments_to_stats(*mom))
            if want_norms:
                met["grad_norm"] = T.global_norm((gc, gs))
                met["update_norm"] = T.global_norm((cu, su))
            return (client_params, server_params, c_opt, s_opt, loss,
                    met)
        return step_obs, False

    def step(client_params, server_params, c_opt, s_opt, batch, key=None,
             weights=None):
        def loss_fn(cp, sp):
            params = {"front": cp["front"], "middle": sp}
            if nls:
                params["tail"] = cp["tail"]
            return adapter.full_loss(params, batch, boundary=base_boundary,
                                     weights=weights)

        loss, (gc, gs) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            client_params, server_params)
        client_params, c_opt, _ = _apply_update(opt_client, gc, c_opt,
                                                client_params)
        server_params, s_opt, _ = _apply_update(opt_server, gs, s_opt,
                                                server_params)
        return client_params, server_params, c_opt, s_opt, loss
    return step, False


def sflv3_step_fn(adapter: SplitAdapter, opt_client: O.Optimizer,
                  opt_server: O.Optimizer, n_clients: int, transport=None,
                  privacy=None, client_weights=None, mesh_axis=None,
                  telemetry=None):
    """Pure SplitFedv3 step (paper Algorithm 1, batch-synchronous form):
    clients run in parallel (vmap over the stacked client axis); the server
    segment is updated once with the weighted average of per-client server
    gradients; client segments update individually (never averaged).

    ``n_clients`` is the LOCAL stacked row count the step vmaps over.
    ``client_weights`` (a GLOBAL 0/1 mask over all rows, e.g.
    ``Placement.client_weights``) excludes phantom padding rows from the
    server average and the reported loss — with weights of all ones (or
    None) the math is EXACTLY the unweighted step.  Under ``mesh_axis``
    (the compiled engine's ``shard_map`` over the "hosp" mesh) each device
    holds ``n_clients`` of the global rows: per-client keys and weights
    index by the GLOBAL row (``axis_index * n_clients + local``), and the
    server-gradient average is completed with a ``psum`` — so the update
    is bit-comparable to the single-device step regardless of the chunking.

    Returns ``(step, keyed)`` with ``step(stacked_clients, server_params,
    c_opt, s_opt, stacked_batch, key=None)``.  A privacy config makes the
    step keyed: every client clips and noises its OWN per-example gradients
    (per-client keys are ``fold_in(step_key, global_client_idx)``, so a
    real hospital's draws do not depend on how many padding rows ride
    along) before the server averages, so each hospital's DP guarantee
    stands on its own.

    With a ``telemetry`` spec the step returns one extra trailing metric
    dict of per-client ``[n_clients]`` float32 taps (cut-layer payload
    stats, joint client+server grad/update norms, DP clip fractions) —
    pure observation of intermediates, params stay bit-identical.
    """
    import jax.numpy as jnp
    nls = adapter.nls
    boundary = transport.boundary if transport is not None else None
    fuse_codec = transport.fused_codec if transport is not None else None
    priv = (privacy if privacy is not None and
            (privacy.dp_enabled or privacy.cut_noise_std > 0) else None)
    if telemetry is not None:
        from repro.obs import telemetry as T
        tel_keys = telemetry.step_keys(
            dp=priv is not None and priv.dp_enabled, cut=True)
        want_cut = "cut_mean" in tel_keys
        want_clip = "clip_frac" in tel_keys
        want_norms = "grad_norm" in tel_keys
    w_global = (np.ones((n_clients,), np.float32)
                if client_weights is None
                else np.asarray(client_weights, np.float32))
    w_sum = float(w_global.sum())

    def _local_rows():
        """(row offset, local weight column) for this device's chunk."""
        off = (0 if mesh_axis is None
               else jax.lax.axis_index(mesh_axis) * n_clients)
        w = jax.lax.dynamic_slice(jnp.asarray(w_global), (off,),
                                  (n_clients,))
        return off, w

    def _server_mean(gs_local):
        if mesh_axis is None:
            return gs_local
        return jax.lax.psum(gs_local, mesh_axis)

    def _weighted_server_mean(g_server, w_local):
        """DP path: the weighted mean of per-client server gradients."""
        with jax.named_scope("update"):
            return _server_mean(jax.tree.map(
                lambda x: (x * w_local.reshape((-1,) + (1,) * (x.ndim - 1))
                           ).sum(axis=0) / w_sum, g_server))

    if priv is not None:
        from repro.privacy.dpsgd import boundary_with_key, dp_value_and_grad

        if telemetry is not None:
            def dp_step_obs(stacked_clients, server_params, c_opt, s_opt,
                            stacked_batch, key=None, gids=None):
                off, w_local = _local_rows()
                # under participation each slot keys by its GLOBAL hospital
                # id, so a hospital's DP draws are co-sample independent
                rows = (gids.astype(jnp.uint32) if gids is not None
                        else (off + jnp.arange(n_clients)).astype(jnp.uint32))
                keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(rows)

                def loss_fn(both, b, k):
                    params = {"front": both["c"]["front"],
                              "middle": both["s"]}
                    if nls:
                        params["tail"] = both["c"]["tail"]
                    sink = []
                    bnd = boundary_with_key(boundary, priv, k,
                                            codec=fuse_codec)
                    if want_cut:
                        bnd = T.observing_boundary(bnd, sink)
                    loss = adapter.full_loss(params, b, boundary=bnd)
                    if want_cut:
                        return loss, T.payload_moments(sink[0])
                    return loss

                if priv.dp_enabled:
                    vg = dp_value_and_grad(loss_fn, priv, has_aux=want_cut,
                                           with_norms=want_clip)
                else:
                    vg = jax.value_and_grad(loss_fn, has_aux=want_cut)

                def one(cp, b, k):
                    met_c = {}
                    if priv.dp_enabled:
                        out = vg({"c": cp, "s": server_params}, b, k)
                        loss, g = out[0], out[1]
                        if want_cut:
                            met_c.update(T.moments_to_stats(
                                *T.combine_moments(*out[2]["aux"])))
                        if want_clip:
                            met_c["clip_frac"] = T.clip_fraction(
                                out[2]["norms"], priv.clip_norm)
                    elif want_cut:
                        (loss, mom), g = vg({"c": cp, "s": server_params},
                                            b, k)
                        met_c.update(T.moments_to_stats(*mom))
                    else:
                        loss, g = vg({"c": cp, "s": server_params}, b, k)
                    if want_norms:
                        met_c["grad_norm"] = T.global_norm(g)
                    return loss, g, met_c

                losses, g, met = jax.vmap(one)(stacked_clients,
                                               stacked_batch, keys)
                gc = g["c"]                      # already per-client grads
                gs = _weighted_server_mean(g["s"], w_local)
                stacked_clients, c_opt, cu = _apply_update(
                    opt_client, gc, c_opt, stacked_clients)
                server_params, s_opt, su = _apply_update(
                    opt_server, gs, s_opt, server_params)
                if want_norms:
                    met["update_norm"] = jnp.sqrt(
                        jax.vmap(lambda u: jnp.square(T.global_norm(u)))(cu)
                        + jnp.square(T.global_norm(su)))
                return (stacked_clients, server_params, c_opt, s_opt,
                        losses, met)
            return dp_step_obs, True

        def dp_step(stacked_clients, server_params, c_opt, s_opt,
                    stacked_batch, key=None, gids=None):
            off, w_local = _local_rows()
            rows = (gids.astype(jnp.uint32) if gids is not None
                    else (off + jnp.arange(n_clients)).astype(jnp.uint32))
            keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(rows)

            def loss_fn(both, b, k):
                params = {"front": both["c"]["front"], "middle": both["s"]}
                if nls:
                    params["tail"] = both["c"]["tail"]
                return adapter.full_loss(
                    params, b, boundary=boundary_with_key(boundary, priv, k,
                                                          codec=fuse_codec))

            vg = (dp_value_and_grad(loss_fn, priv) if priv.dp_enabled
                  else jax.value_and_grad(loss_fn))

            def one(cp, b, k):
                return vg({"c": cp, "s": server_params}, b, k)

            losses, g = jax.vmap(one)(stacked_clients, stacked_batch, keys)
            gc = g["c"]                          # already per-client grads
            gs = _weighted_server_mean(g["s"], w_local)
            stacked_clients, c_opt, _ = _apply_update(
                opt_client, gc, c_opt, stacked_clients)
            server_params, s_opt, _ = _apply_update(opt_server, gs, s_opt,
                                                    server_params)
            return (stacked_clients, server_params, c_opt, s_opt, losses)
        return dp_step, True

    if telemetry is not None:
        def step_obs(stacked_clients, server_params, c_opt, s_opt,
                     stacked_batch, key=None, gids=None):
            del gids  # keyless step: slot identity only affects PRNG rows
            _, w_local = _local_rows()

            def client_loss(cp, sp, batch):
                params = {"front": cp["front"], "middle": sp}
                if nls:
                    params["tail"] = cp["tail"]
                sink = []
                bnd = (T.observing_boundary(boundary, sink) if want_cut
                       else boundary)
                loss = adapter.full_loss(params, batch, boundary=bnd)
                return loss, (T.payload_moments(sink[0]) if want_cut
                              else ())

            def mean_loss(sc, sp):
                losses, moms = jax.vmap(
                    lambda cp, b: client_loss(cp, sp, b))(sc, stacked_batch)
                return (losses * w_local).sum() / w_sum, (losses, moms)

            (_, (losses, moms)), (gc, gs) = jax.value_and_grad(
                mean_loss, argnums=(0, 1), has_aux=True)(stacked_clients,
                                                         server_params)
            with jax.named_scope("update"):
                gc = jax.tree.map(lambda g: g * w_sum, gc)
                gs = _server_mean(gs)
            new_sc, c_opt, cu = _apply_update(opt_client, gc, c_opt,
                                              stacked_clients)
            new_sp, s_opt, su = _apply_update(opt_server, gs, s_opt,
                                              server_params)
            met = {}
            if want_cut:
                met.update(T.moments_to_stats(*moms))
            if want_norms:
                # per-client joint norm: own segment grad + the (shared)
                # mean server grad — the update each hospital experiences
                met["grad_norm"] = jnp.sqrt(
                    jax.vmap(lambda g_: jnp.square(T.global_norm(g_)))(gc)
                    + jnp.square(T.global_norm(gs)))
                met["update_norm"] = jnp.sqrt(
                    jax.vmap(lambda u: jnp.square(T.global_norm(u)))(cu)
                    + jnp.square(T.global_norm(su)))
            return new_sc, new_sp, c_opt, s_opt, losses, met
        return step_obs, False

    def step(stacked_clients, server_params, c_opt, s_opt, stacked_batch,
             key=None, gids=None):
        del gids  # keyless step: slot identity only affects PRNG rows
        _, w_local = _local_rows()

        def client_loss(cp, sp, batch):
            params = {"front": cp["front"], "middle": sp}
            if nls:
                params["tail"] = cp["tail"]
            return adapter.full_loss(params, batch, boundary=boundary)

        def mean_loss(sc, sp):
            losses = jax.vmap(lambda cp, b: client_loss(cp, sp, b))(
                sc, stacked_batch)
            return (losses * w_local).sum() / w_sum, losses

        (loss, losses), (gc, gs) = jax.value_and_grad(
            mean_loss, argnums=(0, 1), has_aux=True)(stacked_clients,
                                                     server_params)
        # gc is stacked per-client (weighted-mean grad => scale back to
        # per-client; a zero-weight phantom row's grad is exactly zero, so
        # the uniform w_sum rescale leaves it zero)
        with jax.named_scope("update"):
            gc = jax.tree.map(lambda g: g * w_sum, gc)
            gs = _server_mean(gs)
        stacked_clients, c_opt, _ = _apply_update(opt_client, gc, c_opt,
                                                  stacked_clients)
        server_params, s_opt, _ = _apply_update(opt_server, gs, s_opt,
                                                server_params)
        return stacked_clients, server_params, c_opt, s_opt, losses
    return step, False


# ---------------------------------------------------------------------------
# jitted step builders — the stepwise engine's per-batch dispatch wrappers
# ---------------------------------------------------------------------------

def make_full_step(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                   telemetry=None):
    """Jitted plain step (centralized / FL local); see ``full_step_fn``.
    With DP the returned step takes a fourth ``key`` argument; with
    ``telemetry`` it returns a trailing metric dict."""
    step, keyed_ = full_step_fn(adapter, opt, privacy, telemetry)
    if keyed_:
        return jax.jit(lambda p, s, b, k: step(p, s, b, k))
    return jax.jit(lambda p, s, b: step(p, s, b))


def make_split_step(adapter: SplitAdapter, opt_client: O.Optimizer,
                    opt_server: O.Optimizer, transport=None, privacy=None,
                    telemetry=None):
    """Jitted SL/SFLv2 step; see ``split_step_fn``.  A privacy config adds
    a sixth ``key`` argument; ``telemetry`` adds a trailing metric dict."""
    step, keyed_ = split_step_fn(adapter, opt_client, opt_server, transport,
                                 privacy, telemetry)
    if keyed_:
        return jax.jit(lambda cp, sp, co, so, b, k: step(cp, sp, co, so, b,
                                                         k))
    return jax.jit(lambda cp, sp, co, so, b: step(cp, sp, co, so, b))


def make_sflv3_step(adapter: SplitAdapter, opt_client: O.Optimizer,
                    opt_server: O.Optimizer, n_clients: int, transport=None,
                    privacy=None, telemetry=None):
    """Jitted SplitFedv3 step; see ``sflv3_step_fn``.  A privacy config
    adds a sixth ``key`` argument; ``telemetry`` adds a trailing metric
    dict."""
    step, keyed_ = sflv3_step_fn(adapter, opt_client, opt_server, n_clients,
                                 transport, privacy, telemetry=telemetry)
    if keyed_:
        return jax.jit(lambda sc, sp, co, so, b, k: step(sc, sp, co, so, b,
                                                         k))
    return jax.jit(lambda sc, sp, co, so, b: step(sc, sp, co, so, b))


__all__ = ["Strategy", "EpochLog", "np_batches", "tree_mean",
           "tree_weighted_mean", "stack_trees", "unstack_tree",
           "full_step_fn", "split_step_fn", "sflv3_step_fn",
           "make_full_step", "make_split_step", "make_sflv3_step"]
