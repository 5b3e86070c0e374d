"""Compiled multi-hospital execution engine.

The stepwise engine (the legacy path in each strategy, kept as the parity
reference) dispatches one jitted step per mini-batch per hospital from a
Python host loop — wall-clock is dominated by dispatch overhead and
hospitals run strictly sequentially.  This module lowers a whole
multi-epoch training RUN (the ``make_*_run`` builders) into a single XLA
program instead:

  * **pad-and-mask layout** — each hospital's shuffled epoch is packed into
    rectangular ``[n_clients, n_batches, batch, ...]`` arrays plus a
    ``[n_clients, n_batches]`` validity mask; uneven hospital sizes become
    masked (no-op) scan steps and, with ``drop_remainder=False``, the final
    short batch becomes per-example weights instead of a ragged shape.
    An unsharded, non-participating SL/SFLv2/SFLv3/v1 whole run takes
    the **index layout** instead (``pack_run_index``): the hospitals' own
    arrays and an int32 ``[n_epochs, n_clients, n_batches, batch]`` grid
    of row ids, from which the program gathers each step's batch on the
    device; the host copies no image bytes.  ``pack_run_inputs`` is the
    one place that chooses between the two for the split family; every
    other run takes the packed grid.
  * **scan over batches, vmap over hospitals** where semantics allow it:
    FL local epochs are independent per hospital, so the per-client
    ``lax.scan`` is wrapped in a ``vmap`` over the stacked hospital axis.
  * **scanned interleave** where they don't: the SL/SFLv2 server segment
    (and its Adam state) is shared and updated sequentially in schedule
    order, so the epoch is ONE ``lax.scan`` over the dense
    ``[step] -> (client, batch)`` schedule array from
    ``repro.core.schedule.schedule_array`` — exact sequential Adam
    semantics, zero host dispatches.
  * **per-step PRNG keys by fold-in on the scan index**: the stepwise path
    draws key ``fold_in(base, t)`` for the t-th step of the run; the packer
    reserves the same running counter (``Strategy._take_key_indices``) and
    the scan body folds the reserved index in, so DP-SGD / cut-layer noise
    draws are bit-identical across engines.
  * **scan over rounds** (``make_fl_run`` / ``make_seq_run`` /
    ``make_interleaved_run`` / ``make_sflv3_run``): an outer ``lax.scan``
    over the epoch axis of ``pack_run``'s ``[n_epochs, ...]`` batch stack
    wraps the epoch body, with the FedAvg weighted aggregation (secagg
    off) and the SFLv2/v1 client-segment averaging folded into the round
    body — a whole ``Strategy.run(n_epochs)`` becomes ONE host dispatch,
    and per-round losses come back stacked ``[n_epochs, ...]`` for the
    per-round ``EpochLog``s.  Per-round key-index grids keep consuming the
    same running counter, epoch-major, so keyed draws stay bit-identical
    to a stepwise multi-epoch loop.

Every scan body calls the SAME pure step functions
(``repro.core.strategies.base.{full,split,sflv3}_step_fn``) the stepwise
jit wrappers use, which is what makes the two engines numerically
equivalent (asserted at 1e-5 in tests/test_engine.py).

Device placement of the hospital axis lives in ``repro.core.placement``:
a ``Placement`` pads ``n_clients`` up to a device multiple with phantom
hospitals (``pack_epoch(pad_clients=...)``) and ``device_put``s every
``[C, ...]`` stack across a 1-D ``("hosp",)`` mesh; on a single device it
is a no-op, so the engine runs unchanged on one CPU and scales to a
multi-device host.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregate import (mean_sync as _mean_sync,
                                  stacked_mean_sync, stacked_weighted_mean,
                                  weighted_mean_normalized as _weighted_mean)
from repro.core.partition import (SplitAdapter, tree_put, tree_select,
                                  tree_take)
from repro.core.strategies.base import (full_step_fn, sflv3_step_fn,
                                        split_step_fn)
from repro import optim as O


# ---------------------------------------------------------------------------
# pad-and-mask epoch packing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedEpoch:
    """One epoch of every hospital's data in rectangular form.

    ``batches[k]`` has shape ``[n_clients, nb_max, batch, ...]``; rows past
    a hospital's real data are zero padding flagged invalid by ``mask``.
    ``pack_run_index`` returns the meta alone, with ``batches`` empty.
    ``ex_weights`` (only with ``drop_remainder=False``) carries per-example
    validity for the final short batch of each hospital.
    """
    batches: dict
    mask: np.ndarray                       # [C, NB] bool
    ex_weights: np.ndarray | None          # [C, NB, B] float32
    n_batches: list
    step_examples: list                    # per client: valid-example counts
    n_samples: list
    batch_size: int

    @property
    def nb_max(self) -> int:
        return self.mask.shape[1]

    @property
    def total_steps(self) -> int:
        return int(sum(self.n_batches))


def _client_batch_count(n: int, batch_size: int,
                        drop_remainder: bool) -> tuple[int, int, int]:
    """``(nb, nb_full, rem)`` for one hospital of ``n`` samples — THE
    batching rule (mirroring ``np_batches``), shared by ``pack_epoch``
    and ``empty_run`` so the two can never drift."""
    nb_full, rem = divmod(n, batch_size)
    return nb_full + (1 if rem and not drop_remainder else 0), nb_full, rem


def _no_span(name, **args):
    return contextlib.nullcontext()


def pack_epoch(client_data: list, batch_size: int,
               rng: np.random.Generator | None,
               drop_remainder: bool = True,
               pad_clients: int = 0, span=_no_span) -> PackedEpoch:
    """Shuffle + pack every hospital's epoch (mirrors ``np_batches``).

    The per-client shuffles consume ``rng`` in hospital order — exactly the
    draws the stepwise path makes — so both engines train on identical
    batch compositions.

    ``pad_clients`` appends that many *phantom hospitals* (zero samples,
    zero batches, all-False mask rows) so the hospital axis reaches a
    device multiple for ``core.placement`` — phantom rows are masked
    no-ops in every scan and carry zero weight in every aggregation.

    ``span`` (``Strategy._span``) times the shuffle and copy as ``gather``.
    """
    with span("gather"):
        return _pack_epoch(client_data, batch_size, rng, drop_remainder,
                           pad_clients)


def _shuffle_epoch(client_data, batch_size, rng, drop_remainder,
                   pad_clients=0):
    """One epoch's shuffled row ids per hospital, cut to the rows its
    batches use, and the epoch's ``PackedEpoch`` meta with ``batches``
    still empty.  Consumes ``rng`` in hospital order, as ``np_batches``
    does."""
    n_batches, n_samples, step_examples, order = [], [], [], []
    for d in client_data:
        n = len(next(iter(d.values())))
        idx = np.arange(n)
        if rng is not None:
            rng.shuffle(idx)
        nb, nb_full, rem = _client_batch_count(n, batch_size,
                                               drop_remainder)
        used = nb_full * batch_size if drop_remainder else n
        order.append(idx[:used])
        n_batches.append(nb)
        n_samples.append(n)
        step_examples.append([batch_size] * nb_full
                             + ([rem] if nb > nb_full else []))
    NB = max(n_batches, default=0)
    n_batches += [0] * pad_clients
    n_samples += [0] * pad_clients
    step_examples += [[] for _ in range(pad_clients)]
    C = len(client_data) + pad_clients

    mask = np.zeros((C, NB), bool)
    ex_w = (None if drop_remainder
            else np.zeros((C, NB, batch_size), np.float32))
    for c in range(C):
        mask[c, :n_batches[c]] = True
        if ex_w is not None:
            for j, m in enumerate(step_examples[c]):
                ex_w[c, j, :m] = 1.0
    return order, PackedEpoch({}, mask, ex_w, n_batches, step_examples,
                              n_samples, batch_size)


def _pack_epoch(client_data, batch_size, rng, drop_remainder, pad_clients):
    order, packed = _shuffle_epoch(client_data, batch_size, rng,
                                   drop_remainder, pad_clients)
    C, NB = packed.mask.shape
    for k in client_data[0]:
        proto = client_data[0][k]
        out = np.zeros((C, NB * batch_size, *proto.shape[1:]), proto.dtype)
        for c, d in enumerate(client_data):
            out[c, :len(order[c])] = d[k][order[c]]
        packed.batches[k] = out.reshape(C, NB, batch_size, *proto.shape[1:])
    return packed


# ---------------------------------------------------------------------------
# compiled epoch kernels
# ---------------------------------------------------------------------------

def _step_key(base_key, idx, keyed):
    if not keyed:
        return None
    from repro.privacy.dpsgd import step_key
    return step_key(base_key, idx)


def _fl_epoch_body(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                   placement=None, telemetry=None):
    """Traceable FL round: vmap-over-hospitals of scan-over-batches.
    Shared verbatim by ``make_fl_epoch`` (host-aggregated rounds) and
    ``make_fl_run``'s round scan — one definition is what keeps the two
    numerically identical.

    With an enabled ``placement`` the hospital axis runs under
    ``shard_map`` on the "hosp" mesh: each device vmaps over its own
    hospital chunk with the global params replicated.  (The XLA SPMD
    partitioner cannot split the grouped-conv lowering of a vmapped CNN
    along the mapped axis, so per-device chunking is done explicitly —
    local epochs are independent, so no collectives are needed.)

    With a ``telemetry`` spec the observed step's metric dict rides the
    scan as an extra output — the epoch returns one extra trailing
    ``met`` dict of ``[C, NB]`` arrays (sharded like the losses under
    placement).  Params are bit-identical: the update math is untouched,
    only additional outputs are stacked.
    """
    step, keyed = full_step_fn(adapter, opt, privacy, telemetry)
    observed = telemetry is not None

    def all_clients(gp, bk, batches, mask, ex_w, key_idx):
        def per_client(b_c, m_c, w_c, ki_c):
            def body(carry, xs):
                p, s = carry
                batch, m, w, ki = xs
                out = step(p, s, batch, _step_key(bk, ki, keyed), w)
                p2, s2, loss = out[0], out[1], out[2]
                ys = (loss, out[3]) if observed else loss
                return (tree_select(m, p2, p), tree_select(m, s2, s)), ys

            (p, _), ys = jax.lax.scan(
                body, (gp, opt.init(gp)), (b_c, m_c, w_c, ki_c))
            return (p, *ys) if observed else (p, ys)

        return jax.vmap(per_client)(batches, mask, ex_w, key_idx)

    def epoch(global_params, batches, mask, ex_w, key_idx, base_key):
        if placement is None or not placement.enabled:
            return all_clients(global_params, base_key, batches, mask,
                               ex_w, key_idx)
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        H = P("hosp")
        sm = shard_map(all_clients, mesh=placement.mesh,
                       in_specs=(P(), P(), H, H, H, H),
                       out_specs=(H, H, H) if observed else (H, H),
                       check_rep=False)
        return sm(global_params, base_key, batches, mask, ex_w, key_idx)

    return epoch


def make_fl_epoch(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                  placement=None, telemetry=None):
    """FL round as vmap-over-hospitals of scan-over-batches, for the
    rounds whose aggregation runs on the host: secure aggregation, or an
    aggregator that is not ``scan_compatible`` (``FedAvg._whole_run``).
    These are its only users; every other run takes ``make_fl_run``.

    Every hospital starts from the broadcast global params with a fresh
    optimizer (FedAvg semantics); masked steps are no-ops via
    ``tree_select`` so the Adam step counter never advances on padding.
    Returns ``epoch(global_params, batches, mask, ex_w, key_idx, base_key)
    -> (stacked local params, [C, NB] losses)`` — plus a trailing ``met``
    dict of ``[C, NB]`` metric taps with a ``telemetry`` spec.
    """
    return jax.jit(_fl_epoch_body(adapter, opt, privacy, placement,
                                  telemetry))


def _seq_epoch_body(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                    telemetry=None):
    """Traceable centralized epoch: one scan-over-batches with persistent
    optimizer state; ``make_seq_run`` scans it over epochs."""
    step, keyed = full_step_fn(adapter, opt, privacy, telemetry)
    observed = telemetry is not None

    def epoch(params, opt_state, batches, mask, ex_w, key_idx, base_key):
        def body(carry, xs):
            p, s = carry
            batch, m, w, ki = xs
            out = step(p, s, batch, _step_key(base_key, ki, keyed), w)
            p2, s2, loss = out[0], out[1], out[2]
            ys = (loss, out[3]) if observed else loss
            return (tree_select(m, p2, p), tree_select(m, s2, s)), ys

        (params, opt_state), ys = jax.lax.scan(
            body, (params, opt_state), (batches, mask, ex_w, key_idx))
        if observed:
            return (params, opt_state, *ys)
        return params, opt_state, ys

    return epoch


def _interleaved_epoch_body(adapter: SplitAdapter, opt_client: O.Optimizer,
                            opt_server: O.Optimizer, transport=None,
                            privacy=None, telemetry=None):
    """Traceable SL/SFLv2 epoch: ONE scan over the dense schedule array;
    ``make_interleaved_run`` scans it over epochs.

    The shared server segment forces sequential semantics: each scan step
    gathers client ``c``'s segment + optimizer slice from the stacked
    hospital axis, runs the exact split step, and scatters the update
    back."""
    step, keyed = split_step_fn(adapter, opt_client, opt_server, transport,
                                privacy, telemetry)
    observed = telemetry is not None

    def epoch(stacked_clients, server, stacked_c_opts, s_opt, batches,
              ex_w, sched, key_idx, base_key, rows=None):
        # rows ([C, NB, B] global row ids) given: ``batches`` lists each
        # key's hospital arrays, and each step reads its rows from the
        # arrays of the hospital it trains
        def body(carry, xs):
            sc, sp, co, so = carry
            cb, ki = xs
            c, b = cb[0], cb[1]
            if rows is None:
                batch = jax.tree.map(lambda x: x[c, b], batches)
            else:
                batch = _hospital_rows(batches, rows[c, b], c)
            w = None if ex_w is None else ex_w[c, b]
            out = step(
                tree_take(sc, c), sp, tree_take(co, c), so, batch,
                _step_key(base_key, ki, keyed), w)
            cp, sp, cop, so, loss = out[0], out[1], out[2], out[3], out[4]
            ys = (loss, out[5]) if observed else loss
            return (tree_put(sc, c, cp), sp, tree_put(co, c, cop), so), ys

        carry, ys = jax.lax.scan(
            body, (stacked_clients, server, stacked_c_opts, s_opt),
            (sched, key_idx))
        return (*carry, *ys) if observed else (*carry, ys)

    return epoch


def _hospital_rows(data, rows, c):
    """Hospital ``c``'s batch, read from its own arrays: ``data[k]`` lists
    the hospitals' ``k`` arrays and ``rows`` holds global row ids into
    them laid end to end.  One ``lax.switch`` branch a hospital reads that
    hospital's arrays alone, so the program holds no concatenated copy of
    the data.  A hospital without rows is never scheduled; its branch
    gives zeros."""
    sizes = [len(a) for a in next(iter(data.values()))]
    offsets = np.cumsum([0] + sizes[:-1])

    def branch(i):
        def take(r):
            if not sizes[i]:
                return {k: jnp.zeros(r.shape + v[i].shape[1:], v[i].dtype)
                        for k, v in data.items()}
            return {k: v[i][r - offsets[i]] for k, v in data.items()}
        return take

    return jax.lax.switch(c, [branch(i) for i in range(len(sizes))], rows)


def _sflv3_epoch_body(adapter: SplitAdapter, opt_client: O.Optimizer,
                      opt_server: O.Optimizer, n_clients: int,
                      transport=None, privacy=None, client_weights=None,
                      placement=None, telemetry=None):
    """Traceable SplitFedv3/v1 epoch: scan over synchronous steps with the
    vmapped per-client step inside; ``make_sflv3_run`` scans it over
    epochs.  ``n_clients`` is the ARRAY hospital count (a
    placement's padded ``c_pad``); ``client_weights`` zeroes phantom rows
    out of the server-gradient average.

    With an enabled ``placement`` the scan runs under ``shard_map``: each
    device scans over its own hospital chunk (vmapped conv programs never
    meet the SPMD partitioner) and the per-step server-gradient average is
    completed with one ``psum`` — the server (and its Adam state) stays
    replicated, client segments and their Adam state stay sharded.
    """
    sharded = placement is not None and placement.enabled
    observed = telemetry is not None
    if sharded:
        local = placement.c_pad // placement.mesh.devices.size
        weights = (placement.client_weights() if client_weights is None
                   else client_weights)
        step, keyed = sflv3_step_fn(adapter, opt_client, opt_server, local,
                                    transport, privacy, weights,
                                    mesh_axis="hosp", telemetry=telemetry)
    else:
        local = n_clients
        step, keyed = sflv3_step_fn(adapter, opt_client, opt_server,
                                    n_clients, transport, privacy,
                                    client_weights, telemetry=telemetry)

    def chunk_epoch(stacked_clients, server, c_opt, s_opt, batches, b_idx,
                    key_idx, base_key, rows=None):
        # rows ([C, NB, B] global row ids) given: ``batches`` holds the
        # hospitals' rows laid end to end and each step gathers from it
        def body(carry, xs):
            sc, sp, co, so = carry
            bi, ki = xs
            if rows is None:
                batch = jax.tree.map(
                    lambda x: x[jnp.arange(local), bi], batches)
            else:
                r = rows[jnp.arange(local), bi]
                batch = jax.tree.map(lambda x: x[r], batches)
            out = step(sc, sp, co, so, batch,
                       _step_key(base_key, ki, keyed))
            ys = (out[4], out[5]) if observed else out[4]
            return out[:4], ys

        carry, ys = jax.lax.scan(
            body, (stacked_clients, server, c_opt, s_opt), (b_idx, key_idx))
        return (*carry, *ys) if observed else (*carry, ys)

    if not sharded:
        return chunk_epoch

    def epoch(stacked_clients, server, c_opt, s_opt, batches, b_idx,
              key_idx, base_key):
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        H = P("hosp")
        SC = P(None, "hosp")       # [steps, C] losses / met taps
        sm = shard_map(
            chunk_epoch, mesh=placement.mesh,
            # c_opt mixes [C, ...] leaves with the scalar Adam count, so it
            # needs per-leaf specs; server + its opt state are replicated
            in_specs=(H, P(), placement.leaf_specs(c_opt), P(), H,
                      P(None, "hosp"), P(), P()),
            out_specs=(H, P(), placement.leaf_specs(c_opt), P(), SC, SC)
            if observed else
            (H, P(), placement.leaf_specs(c_opt), P(), SC),
            check_rep=False)
        return sm(stacked_clients, server, c_opt, s_opt, batches, b_idx,
                  key_idx, base_key)

    return epoch


def _update_cosine(stacked, gp, new_gp, eps=1e-12):
    """Per-hospital cosine between each local FedAvg update delta
    (``local_c - global``) and the aggregated mean delta
    (``new_global - global``) — the round's update-agreement tap
    (traceable; shared by ``make_fl_run``'s round body and the jitted
    host-callable ``update_cosine``).  Zero
    deltas (phantom rows, no-op rounds) report cosine 0."""
    C = jax.tree.leaves(stacked)[0].shape[0]
    deltas = jnp.concatenate(
        [l.reshape(C, -1).astype(jnp.float32) - g.reshape(-1).astype(
            jnp.float32)[None]
         for l, g in zip(jax.tree.leaves(stacked), jax.tree.leaves(gp))],
        axis=1)
    mean_d = jnp.concatenate(
        [(n.astype(jnp.float32) - g.astype(jnp.float32)).reshape(-1)
         for n, g in zip(jax.tree.leaves(new_gp), jax.tree.leaves(gp))])
    num = deltas @ mean_d
    den = (jnp.linalg.norm(deltas, axis=1) * jnp.linalg.norm(mean_d))
    return num / (den + eps)


@jax.jit
def update_cosine(stacked, gp, new_gp):
    """Host-callable ``_update_cosine`` for the host-aggregated and
    stepwise FL rounds (the whole-run engine computes it inside the round
    scan)."""
    return _update_cosine(stacked, gp, new_gp)


# ``stacked_weighted_mean`` / ``stacked_mean_sync`` (and the traceable
# ``_weighted_mean`` / ``_mean_sync`` cores the run builders inline) now
# live in ``repro.core.aggregate`` — imported above, re-exported here for
# the strategies' compiled paths and external callers.


# ---------------------------------------------------------------------------
# whole-run kernels — scan over rounds around the epoch bodies above
# ---------------------------------------------------------------------------

def _donating_jit(fn, donate_argnums):
    """jit the whole-run body with its big buffers donated.

    The run carries (params / optimizer state, which the scan returns with
    identical shapes — XLA aliases them in place) and the packed
    ``[E, C, NB, B, ...]`` batch stack, or the hospital arrays an unsharded
    split-family run gathers from (no aliasable output, but freeing it
    at entry lets the allocator reuse the run's largest buffer as scratch)
    are dead to the caller the moment the run is dispatched: every strategy
    immediately overwrites its state with the outputs.  Donating them cuts
    peak HBM by roughly the input footprint.  XLA warns per donated buffer
    it could not alias (the batch stack, by design) — that warning is
    filtered here, scoped to the call.

    ``.lower`` is re-exposed for ``obs.profile.hlo_cost``, which re-lowers
    the stored invocation from abstract avals (``abstract_args``).
    """
    jfn = jax.jit(fn, donate_argnums=donate_argnums)

    @functools.wraps(fn)
    def wrapper(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jfn(*args)

    wrapper.lower = jfn.lower
    return wrapper


def abstract_args(args):
    """Concrete invocation args -> ``ShapeDtypeStruct`` skeleton.

    What the strategies stash as ``_last_run_invocation``: ``jit.lower``
    accepts the abstract avals, so ``hlo_cost`` can re-lower the exact
    program without the stash pinning the run's donated (deleted) buffers
    or the multi-epoch batch stack in memory.  A committed array keeps its
    sharding, so a ``shard=True`` run re-lowers as the sharded program and
    the stash records where the run's inputs were placed.  A host leaf
    gives the dtype ``jit`` would give it, read without a device copy.
    """
    def abstract(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return a
        if isinstance(a, jax.Array):
            sharding = a.sharding if a.committed else None
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        return jax.ShapeDtypeStruct(
            np.shape(a), jax.dtypes.canonicalize_dtype(np.result_type(a)))

    return jax.tree.map(abstract, args)


def empty_run(sizes, batch_size: int, drop_remainder: bool = True) -> bool:
    """True when no training set of ``sizes`` rows yields a single batch.
    ``Strategy._run`` checks it before any pack, so a run with nothing to
    train compiles nothing and draws only the shuffles its epochs would
    (``Strategy._empty_epoch``)."""
    return not any(_client_batch_count(n, batch_size, drop_remainder)[0]
                   for n in sizes)


def pack_run(client_data, batch_size: int, rng, n_epochs: int,
             drop_remainder: bool = True, pad_clients: int = 0,
             span=_no_span):
    """Pack ``n_epochs`` epochs into ``[n_epochs, n_clients, nb_max, ...]``.

    Consumes ``rng`` exactly as a stepwise loop of per-epoch packs would
    (epoch-major, hospital order inside each epoch), so both engines train
    on identical batch compositions.  Batch counts, masks and per-example
    weights are epoch-invariant (data sizes never change mid-run) — only
    the shuffles differ — so the returned ``PackedEpoch`` meta is the
    first epoch's.  ``pad_clients`` phantom hospitals (see ``pack_epoch``)
    ride along on axis 1.  Memory grows linearly with ``n_epochs`` (the
    whole run's batch grid lives in one buffer); callers with huge runs
    can chunk ``run`` into several calls.  ``span`` times each epoch's
    ``gather`` (``pack_epoch``) and the ``stack`` into one buffer.
    """
    packs = [pack_epoch(client_data, batch_size, rng, drop_remainder,
                        pad_clients, span)
             for _ in range(n_epochs)]
    with span("stack"):
        batches = {k: np.stack([p.batches[k] for p in packs])
                   for k in packs[0].batches}
    return batches, packs[0]


def pack_run_index(client_data, batch_size: int, rng, n_epochs: int,
                   drop_remainder: bool = True, pad_clients: int = 0,
                   span=_no_span):
    """``pack_run``'s batches as row indices: ``(data, idx, meta)``, what
    the unsharded SL/SFLv2 (``make_interleaved_run``) and SFLv3/v1
    (``make_sflv3_run``) whole runs gather their batches from.

    ``data[k]`` lists every hospital's ``k`` array as the caller gave it
    (no copy, no padding).  ``idx`` is an int32 ``[n_epochs, n_clients,
    nb_max, batch]`` grid of global row ids into the hospitals' rows laid
    end to end; padding slots point at row 0 and carry no weight.  The
    shuffles consume ``rng`` exactly as ``pack_run``'s do (epoch-major,
    hospital order), so gathering ``idx`` from the concatenated rows gives
    ``pack_run``'s batch grid row for row; ``meta`` is its ``PackedEpoch``
    meta with ``batches`` empty.  ``pad_clients`` phantom hospitals (see
    ``pack_epoch``) get all-False mask rows and index rows of row 0, where
    the grid has zeros: zero-weight rows either way.  ``span`` times the
    shuffles and the index grid as ``gather``.
    """
    with span("gather"):
        sizes = [len(next(iter(d.values()))) for d in client_data]
        offsets = np.cumsum([0] + sizes[:-1])
        orders = []
        for _ in range(n_epochs):
            order, meta = _shuffle_epoch(client_data, batch_size, rng,
                                         drop_remainder, pad_clients)
            orders.append(order)
        C, NB = meta.mask.shape
        idx = np.zeros((n_epochs, C, NB * batch_size), np.int32)
        for e, order in enumerate(orders):
            for c, rows in enumerate(order):
                idx[e, c, :len(rows)] = offsets[c] + rows
        data = {k: [d[k] for d in client_data] for k in client_data[0]}
    return data, idx.reshape(n_epochs, C, NB, batch_size), meta


def pack_counters(batches: dict, slots: int, real: int,
                  device_gather: int = 0) -> dict:
    """The ``pack`` span's counters: bytes handed to the program per data
    key (a packed grid, or the hospitals' arrays and the ``index`` grid),
    batch slots packed, the real (non-padding) batches among them, and
    the batch slots the program gathers on the device (0 where the host
    packed them)."""
    return {**{f"bytes_{k}": sum(int(a.nbytes) for a in jax.tree.leaves(v))
               for k, v in batches.items()},
            "batch_slots": int(slots), "real_batches": int(real),
            "device_gather": int(device_gather)}


def pack_run_inputs(client_data, batch_size: int, rng, n_epochs: int,
                    drop_remainder: bool, placement, span=_no_span):
    """A split-family whole run's batches, in the layout ``placement``
    takes: the one place that chooses between the two.

    Sharded (``placement.enabled``), each device holds its own hospitals'
    rows, so the host packs ``pack_run``'s grid and the program takes
    ``(grid,)``.  Otherwise the program gathers every batch on the device
    from the hospitals' own arrays and ``pack_run_index``'s row ids:
    ``(data, idx)``.  Both consume ``rng`` alike.

    Returns ``(inputs, example, meta, counters)``: the program's batch
    inputs; a zero batch of the run's batch shape for the wire accounting,
    which reads shapes only; the ``PackedEpoch`` meta; and the ``pack``
    span's counters (``pack_counters``).
    """
    if placement.enabled:
        grid, meta = pack_run(client_data, batch_size, rng, n_epochs,
                              drop_remainder, pad_clients=placement.n_pad,
                              span=span)
        inputs, handed = (grid,), grid
    else:
        data, idx, meta = pack_run_index(client_data, batch_size, rng,
                                         n_epochs, drop_remainder,
                                         pad_clients=placement.n_pad,
                                         span=span)
        inputs, handed = (data, idx), {**data, "index": idx}
    slots = n_epochs * meta.mask.size
    counters = pack_counters(handed, slots, n_epochs * sum(meta.n_batches),
                             0 if placement.enabled else slots)
    example = {k: np.zeros((batch_size, *v.shape[1:]), v.dtype)
               for k, v in client_data[0].items()}
    return inputs, example, meta, counters


def make_fl_run(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                placement=None, telemetry=None, aggregator=None):
    """Whole FL training run as ONE program: ``lax.scan`` over rounds, each
    round the SAME vmap-over-hospitals scan-over-batches body
    ``make_fl_epoch`` jits, followed by the in-graph data-size-weighted
    FedAvg aggregation.  (Secure aggregation needs host-side per-client
    masked uploads and keeps the per-round path.)  Under placement the
    epoch body runs in ``shard_map`` chunks and the FedAvg reduction over
    the sharded hospital axis lowers to one all-reduce per round.
    Phantom rows carry zero aggregation weight.  Returns
    ``run(global_params, batches[E,C,NB,...], mask, ex_w, key_idx[E,C,NB],
    base_key, agg_weights[C]) -> (params, [E,C,NB] losses)``.

    With a ``telemetry`` spec the round body also stacks the step metric
    taps (``met`` dict of ``[E, C, NB]`` arrays) and — when the spec asks
    for ``update_cosine`` — each round's per-hospital cosine between the
    local delta and the aggregated mean delta (``[E, C]``), computed
    in-graph from the stacked locals the round already holds: the run
    stays ONE dispatch and the FedAvg math is untouched.

    ``aggregator=None`` keeps the inlined pre-normalized weighted mean
    (bit-identical to pre-PR-9 programs); a scan-compatible
    ``core.aggregate.Aggregator`` replaces the round reduction in-graph.
    """
    epoch = _fl_epoch_body(adapter, opt, privacy, placement, telemetry)
    observed = telemetry is not None
    want_cos = observed and telemetry.update_cosine

    def fl_run(global_params, batches, mask, ex_w, key_idx, base_key,
               agg_w):
        w = agg_w.astype(jnp.float32) / agg_w.astype(jnp.float32).sum()

        def reduce(stacked, gp):
            with jax.named_scope("update"):
                if aggregator is None:
                    return _weighted_mean(stacked, w)
                return aggregator.aggregate(stacked, agg_w, gp)

        def round_body(gp, xs):
            b_e, ki_e = xs
            if observed:
                stacked, losses, met = epoch(gp, b_e, mask, ex_w, ki_e,
                                             base_key)
                new_gp = reduce(stacked, gp)
                if want_cos:
                    met = dict(met)
                    met["update_cosine"] = _update_cosine(stacked, gp,
                                                          new_gp)
                return new_gp, (losses, met)
            stacked, losses = epoch(gp, b_e, mask, ex_w, ki_e, base_key)
            return reduce(stacked, gp), losses

        return jax.lax.scan(round_body, global_params, (batches, key_idx))

    # donate the param carry (aliased into the output) + the batch stack
    return _donating_jit(fl_run, donate_argnums=(0, 1))


def make_seq_run(adapter: SplitAdapter, opt: O.Optimizer, privacy=None,
                 telemetry=None):
    """Whole centralized run: scan over epochs around the
    scan-over-batches epoch body (persistent optimizer state across
    epochs).
    Returns ``run(params, opt_state, batches[E,NB,...], mask[NB], ex_w,
    key_idx[E,NB], base_key) -> (params, opt_state, [E,NB] losses)`` —
    plus a trailing ``met`` dict of ``[E, NB]`` taps with a ``telemetry``
    spec."""
    epoch = _seq_epoch_body(adapter, opt, privacy, telemetry)
    observed = telemetry is not None

    def seq_run(params, opt_state, batches, mask, ex_w, key_idx, base_key):
        def round_body(carry, xs):
            b_e, ki_e = xs
            out = epoch(*carry, b_e, mask, ex_w, ki_e, base_key)
            ys = (out[2], out[3]) if observed else out[2]
            return (out[0], out[1]), ys

        (params, opt_state), ys = jax.lax.scan(
            round_body, (params, opt_state), (batches, key_idx))
        if observed:
            return (params, opt_state, *ys)
        return params, opt_state, ys

    return _donating_jit(seq_run, donate_argnums=(0, 1, 2))


def make_interleaved_run(adapter: SplitAdapter, opt_client: O.Optimizer,
                         opt_server: O.Optimizer, transport=None,
                         privacy=None, sync_clients: bool = False,
                         client_weights=None, placement=None,
                         telemetry=None):
    """Whole SL/SFLv2 run: scan over epochs around the scanned schedule
    interleave body (``_interleaved_epoch_body``).  ``sync_clients``
    folds the SFLv2 end-of-epoch client fed-averaging into the round
    body (``client_weights`` excludes placement phantom rows from it).
    The schedule array is epoch-invariant (batch counts never
    change) and is rescanned each round; per-epoch key indices arrive as
    ``key_idx[E, steps]``.

    Unsharded, the run gathers its batches on the device from
    ``pack_run_index``'s output, as an unsharded SFLv3 run does:
    ``run(stacked_clients, server, stacked_c_opts, s_opt, data,
    idx[E,C,NB,B], ex_w, sched, key_idx, base_key)``; step ``s`` of epoch
    ``e`` takes the rows ``idx[e, c, b]`` for the ``(c, b)`` that
    ``sched[s]`` names, from hospital ``c``'s own arrays in ``data``.
    With an enabled ``placement`` the run takes ``pack_run``'s grid:
    ``run(stacked_clients, server, stacked_c_opts, s_opt,
    batches[E,C,NB,...], ex_w, sched, key_idx, base_key)``.  Both return
    ``(..., [E, steps] losses)``.
    """
    epoch = _interleaved_epoch_body(adapter, opt_client, opt_server,
                                    transport, privacy, telemetry)
    observed = telemetry is not None
    sync_w = (None if client_weights is None
              else jnp.asarray(client_weights, jnp.float32))

    def rounds(state, xs, ex_w, sched, base_key, data=None):
        """The scan over epochs; ``xs`` is ``(per-epoch batch grids or
        row ids, key_idx)``, and ``data`` the hospitals' arrays."""
        def round_body(carry, xs):
            b_e, ki_e = xs
            if data is None:
                out = epoch(*carry, b_e, ex_w, sched, ki_e, base_key)
            else:
                out = epoch(*carry, data, ex_w, sched, ki_e, base_key, b_e)
            sc, sp, co, so = out[0], out[1], out[2], out[3]
            ys = (out[4], out[5]) if observed else out[4]
            if sync_clients:
                with jax.named_scope("update"):
                    sc = _mean_sync(sc, sync_w)
            return (sc, sp, co, so), ys

        carry, ys = jax.lax.scan(round_body, state, xs)
        return (*carry, *ys) if observed else (*carry, ys)

    if placement is not None and placement.enabled:
        def interleaved_run(stacked_clients, server, stacked_c_opts, s_opt,
                            batches, ex_w, sched, key_idx, base_key):
            return rounds((stacked_clients, server, stacked_c_opts, s_opt),
                          (batches, key_idx), ex_w, sched, base_key)
    else:
        def interleaved_run(stacked_clients, server, stacked_c_opts, s_opt,
                            data, idx, ex_w, sched, key_idx, base_key):
            return rounds((stacked_clients, server, stacked_c_opts, s_opt),
                          (idx, key_idx), ex_w, sched, base_key, data)

    return _donating_jit(interleaved_run, donate_argnums=(0, 1, 2, 3, 4))


def make_sflv3_run(adapter: SplitAdapter, opt_client: O.Optimizer,
                   opt_server: O.Optimizer, n_clients: int, transport=None,
                   privacy=None, sync_clients: bool = False,
                   client_weights=None, placement=None, telemetry=None):
    """Whole SplitFedv3/v1 run: scan over epochs around the synchronous-
    step scan body (``_sflv3_epoch_body``; its ``[steps, C]`` wrap-around
    batch index ``b_idx`` is epoch-invariant); ``sync_clients`` folds
    SFLv1's client fed-averaging into the round body; ``client_weights``
    excludes placement phantom rows from server-gradient averaging and
    syncs.

    Unsharded, the run gathers its batches on the device from
    ``pack_run_index``'s output: ``run(stacked_clients, server, c_opt,
    s_opt, data, idx[E,C,NB,B], b_idx, key_idx[E,steps], base_key)``
    concatenates each key's hospital arrays in ``data`` once and each
    step takes its ``[C, B, ...]`` rows ``idx[e, c, b_idx[s, c]]``.  With
    an enabled ``placement`` each device holds its own hospitals' rows, so
    the run takes ``pack_run``'s grid: ``run(stacked_clients, server,
    c_opt, s_opt, batches[E,C,NB,...], b_idx, key_idx, base_key)``.
    Both return ``(..., [E, steps, C] losses)``."""
    epoch = _sflv3_epoch_body(adapter, opt_client, opt_server, n_clients,
                              transport, privacy, client_weights,
                              placement, telemetry)
    observed = telemetry is not None
    sync_w = (None if client_weights is None
              else jnp.asarray(client_weights, jnp.float32))

    def rounds(state, xs, b_idx, base_key, rows=None):
        """The scan over epochs; ``xs`` is ``(per-epoch batch grids or
        row ids, key_idx)``, and ``rows`` the concatenated data."""
        def round_body(carry, xs):
            b_e, ki_e = xs
            if rows is None:
                out = epoch(*carry, b_e, b_idx, ki_e, base_key)
            else:
                out = epoch(*carry, rows, b_idx, ki_e, base_key, b_e)
            sc, sp, co, so = out[0], out[1], out[2], out[3]
            ys = (out[4], out[5]) if observed else out[4]
            if sync_clients:
                with jax.named_scope("update"):
                    sc = _mean_sync(sc, sync_w)
            return (sc, sp, co, so), ys

        carry, ys = jax.lax.scan(round_body, state, xs)
        return (*carry, *ys) if observed else (*carry, ys)

    if placement is not None and placement.enabled:
        def sflv3_run(stacked_clients, server, c_opt, s_opt, batches,
                      b_idx, key_idx, base_key):
            return rounds((stacked_clients, server, c_opt, s_opt),
                          (batches, key_idx), b_idx, base_key)
    else:
        def sflv3_run(stacked_clients, server, c_opt, s_opt, data, idx,
                      b_idx, key_idx, base_key):
            rows = {k: jnp.concatenate(v) for k, v in data.items()}
            return rounds((stacked_clients, server, c_opt, s_opt),
                          (idx, key_idx), b_idx, base_key, rows)

    return _donating_jit(sflv3_run, donate_argnums=(0, 1, 2, 3, 4))


# ---------------------------------------------------------------------------
# participation: per-round K-of-N subsampling into a fixed slot axis
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ParticipationPack:
    """Per-round packing metadata for a participating run.

    The hospital axis is ``n_slots`` wide (K for fixed-size sampling) and
    every per-round array rides the round scan as an INPUT — who
    participates is data, not shape, so the whole run stays one program
    and compute scales with the slot count, not the federation size.
    ``slot_gid[e, s]`` maps slot ``s`` of round ``e`` to its global
    hospital id (-1 for an empty slot: zero weight, all-False mask);
    ``staleness[e, s]`` counts the rounds that hospital sat out since it
    last participated (0 for fresh/first-time rows);
    ``n_batches`` / ``n_samples`` / ``step_examples`` are per GLOBAL
    hospital (epoch-invariant).
    """
    mask: np.ndarray                    # [E, S, NB] bool
    ex_weights: np.ndarray | None       # [E, S, NB, B] float32
    agg_w: np.ndarray                   # [E, S] float32 (data sizes)
    slot_gid: np.ndarray                # [E, S] int32, -1 = empty slot
    part_mask: np.ndarray               # [E, N] bool
    staleness: np.ndarray               # [E, S] float32
    n_batches: list                     # per global hospital
    n_samples: list                     # per global hospital
    step_examples: list                 # per global hospital
    batch_size: int

    @property
    def nb_max(self) -> int:
        return self.mask.shape[2]

    @property
    def n_slots(self) -> int:
        return self.mask.shape[1]

    @property
    def n_rounds(self) -> int:
        return self.mask.shape[0]

    @property
    def n_global(self) -> int:
        return self.part_mask.shape[1]


def pack_participation_run(client_data, batch_size: int, rng,
                           n_epochs: int, participation,
                           drop_remainder: bool = True, span=_no_span):
    """Pack ``n_epochs`` participating rounds into
    ``[n_epochs, n_slots, nb_max, batch, ...]`` batch stacks.

    Every round consumes the shared data-shuffle ``rng`` for ALL N
    hospitals in global order — exactly the draws ``pack_run`` makes —
    and only then fills slots with the round's sampled hospitals.  A
    hospital's batch composition therefore depends only on (round,
    hospital), never on who else was sampled (co-sample independence),
    and ``Participation(k=N)`` packs arrays bit-identical to
    ``pack_run``'s.  ``nb_max`` is the max batch count over ALL N
    hospitals, so the slot grid never reshapes across rounds.  ``span``
    times the shuffles and copies straight into the grid as ``gather``.
    """
    with span("gather"):
        return _pack_participation_run(client_data, batch_size, rng,
                                       n_epochs, participation,
                                       drop_remainder)


def _pack_participation_run(client_data, batch_size, rng, n_epochs,
                            participation, drop_remainder):
    N = len(client_data)
    if participation.n_global != N:
        raise ValueError(f"participation.n_global={participation.n_global} "
                         f"but {N} hospitals were passed")
    S = participation.n_slots
    ns = [len(next(iter(d.values()))) for d in client_data]
    counts = [_client_batch_count(n, batch_size, drop_remainder)
              for n in ns]
    nbs = [c[0] for c in counts]
    step_examples = [[batch_size] * nb_full + ([rem] if nb > nb_full else [])
                     for nb, nb_full, rem in counts]
    NB = max(nbs, default=0)
    proto = client_data[0]
    batches = {k: np.zeros((n_epochs, S, NB, batch_size, *v.shape[1:]),
                           v.dtype) for k, v in proto.items()}
    mask = np.zeros((n_epochs, S, NB), bool)
    ex_w = (None if drop_remainder
            else np.zeros((n_epochs, S, NB, batch_size), np.float32))
    agg_w = np.zeros((n_epochs, S), np.float32)
    slot_gid = np.full((n_epochs, S), -1, np.int32)
    part_mask = np.zeros((n_epochs, N), bool)
    staleness = np.zeros((n_epochs, S), np.float32)
    last_seen: dict = {}
    for e in range(n_epochs):
        ids = participation.round_ids(e)
        if len(ids) > S:
            raise ValueError(f"round {e} sampled {len(ids)} hospitals but "
                             f"only {S} slots are packed")
        part_mask[e, ids] = True
        orders = []
        for g in range(N):
            idx = np.arange(ns[g])
            if rng is not None:
                rng.shuffle(idx)
            orders.append(idx)
        for s, g in enumerate(ids):
            g = int(g)
            slot_gid[e, s] = g
            agg_w[e, s] = ns[g]
            prev_e = last_seen.get(g)
            staleness[e, s] = 0.0 if prev_e is None else float(e - prev_e - 1)
            mask[e, s, :nbs[g]] = True
            used = nbs[g] * batch_size if drop_remainder else ns[g]
            for k, v in client_data[g].items():
                row = np.zeros((NB * batch_size, *v.shape[1:]), v.dtype)
                row[:used] = v[orders[g][:used]]
                batches[k][e, s] = row.reshape(NB, batch_size,
                                               *v.shape[1:])
            if ex_w is not None:
                for j, m in enumerate(step_examples[g]):
                    ex_w[e, s, j, :m] = 1.0
            last_seen[g] = e
    return batches, ParticipationPack(mask, ex_w, agg_w, slot_gid,
                                      part_mask, staleness, nbs, ns,
                                      step_examples, batch_size)


def make_fl_run_participation(adapter: SplitAdapter, opt: O.Optimizer,
                              privacy=None, telemetry=None,
                              aggregator=None):
    """Whole participating FL run as ONE program.

    Same vmap-over-slots scan-over-batches round body as ``make_fl_run``,
    but every per-round array (batch grid, mask, per-example weights,
    key-index grid, aggregation weights, staleness, slot->gid map) is a
    round-scan INPUT: empty slots are all-False-mask zero-weight phantom
    rows, and the aggregation runs in-graph through ``aggregator`` (whose
    zero-total guard keeps the previous globals on a no-client Poisson
    round).  Returns ``run(global_params, batches[E,S,NB,...], mask,
    ex_w, key_idx[E,S,NB], base_key, agg_w[E,S], staleness[E,S],
    slot_gid[E,S]) -> (params, [E,S,NB] losses)`` (plus a ``met`` dict
    with a ``telemetry`` spec, as in ``make_fl_run``).
    """
    epoch = _fl_epoch_body(adapter, opt, privacy, None, telemetry)
    observed = telemetry is not None
    want_cos = observed and telemetry.update_cosine

    def fl_run_participation(global_params, batches, mask, ex_w, key_idx,
                             base_key, agg_w, staleness, slot_gid):
        def round_body(gp, xs):
            b_e, m_e, w_e, ki_e, aw_e, st_e, gid_e = xs
            if observed:
                stacked, losses, met = epoch(gp, b_e, m_e, w_e, ki_e,
                                             base_key)
            else:
                stacked, losses = epoch(gp, b_e, m_e, w_e, ki_e, base_key)
            with jax.named_scope("update"):
                new_gp = aggregator.aggregate(stacked, aw_e, gp, st_e,
                                              gid_e)
            if not observed:
                return new_gp, losses
            if want_cos:
                met = dict(met)
                met["update_cosine"] = _update_cosine(stacked, gp, new_gp)
            return new_gp, (losses, met)

        return jax.lax.scan(
            round_body, global_params,
            (batches, mask, ex_w, key_idx, agg_w, staleness, slot_gid))

    return _donating_jit(fl_run_participation, donate_argnums=(0, 1))


def _slot_mean_sync(sc, gid_e):
    """Broadcast the sampled slots' mean client segment to every global
    row (SFLv2's single global client segment under participation)."""
    w_slots = (gid_e >= 0).astype(jnp.float32)
    rows = jax.tree.map(lambda x: x[jnp.maximum(gid_e, 0)], sc)
    wn = w_slots / jnp.maximum(w_slots.sum(), 1.0)

    def leaf(x):
        wx = wn.reshape((-1,) + (1,) * (x.ndim - 1))
        return (x.astype(jnp.float32) * wx).sum(axis=0)

    m = jax.tree.map(leaf, rows)
    return jax.tree.map(
        lambda x, mm: jnp.broadcast_to(mm.astype(x.dtype)[None], x.shape),
        sc, m)


def make_interleaved_run_participation(adapter: SplitAdapter,
                                       opt_client: O.Optimizer,
                                       opt_server: O.Optimizer,
                                       n_global: int, transport=None,
                                       privacy=None,
                                       sync_clients: bool = False):
    """Whole participating SL/SFLv2 run as ONE program.

    All N client segments (and their optimizer slices) persist in the
    ``[N, ...]`` carry; each round's dense schedule covers only the
    sampled slots and rides the scan as ``sched[E, steps, 3]`` rows of
    ``(slot, batch, valid)`` — each step gathers the slot's GLOBAL row
    via ``slot_gid``, runs the exact split step, and scatters back;
    invalid padding rows are ``tree_select`` no-ops.  ``sync_clients``
    (SFLv2) broadcasts the sampled slots' post-round mean to every
    global row — the single-global-client-segment semantics.  Returns
    ``run(stacked_clients[N,...], server, stacked_c_opts, s_opt,
    batches[E,S,NB,...], ex_w, sched, key_idx[E,steps], base_key,
    slot_gid[E,S]) -> (..., [E, steps] losses)``.
    """
    step, keyed = split_step_fn(adapter, opt_client, opt_server, transport,
                                privacy)

    def interleaved_run_participation(stacked_clients, server,
                                      stacked_c_opts, s_opt, batches, ex_w,
                                      sched, key_idx, base_key, slot_gid):
        def round_body(carry, xs):
            sc0, sp0, co0, so0 = carry
            b_e, w_e, sched_e, ki_e, gid_e = xs

            def body(c2, xs2):
                sc, sp, co, so = c2
                row, ki = xs2
                slot, b, valid = row[0], row[1], row[2]
                g = jnp.maximum(gid_e[slot], 0)
                batch = jax.tree.map(lambda x: x[slot, b], b_e)
                w = None if w_e is None else w_e[slot, b]
                cp, cop = tree_take(sc, g), tree_take(co, g)
                out = step(cp, sp, cop, so, batch,
                           _step_key(base_key, ki, keyed), w)
                v = valid > 0
                cp2 = tree_select(v, out[0], cp)
                sp2 = tree_select(v, out[1], sp)
                cop2 = tree_select(v, out[2], cop)
                so2 = tree_select(v, out[3], so)
                loss = jnp.where(v, out[4], 0.0)
                return (tree_put(sc, g, cp2), sp2,
                        tree_put(co, g, cop2), so2), loss

            (sc, sp, co, so), losses = jax.lax.scan(
                body, (sc0, sp0, co0, so0), (sched_e, ki_e))
            if sync_clients:
                with jax.named_scope("update"):
                    sc = _slot_mean_sync(sc, gid_e)
            return (sc, sp, co, so), losses

        carry, losses = jax.lax.scan(
            round_body, (stacked_clients, server, stacked_c_opts, s_opt),
            (batches, ex_w, sched, key_idx, slot_gid))
        return (*carry, losses)

    return _donating_jit(interleaved_run_participation,
                         donate_argnums=(0, 1, 2, 3, 4))


def make_sflv3_run_participation(adapter: SplitAdapter,
                                 opt_client: O.Optimizer,
                                 opt_server: O.Optimizer, k_slots: int,
                                 n_global: int, transport=None,
                                 privacy=None, sync_clients: bool = False):
    """Whole participating SplitFedv3/v1 run as ONE program.

    The round body gathers the sampled slots' client segments (and their
    optimizer rows) out of the persistent ``[N, ...]`` stacks via
    ``slot_gid``, runs the synchronous slot-wide step scan (per-step DP
    keys fold in the GLOBAL hospital id through the step fn's ``gids``
    hook, so draws are co-sample independent), and scatters the trained
    rows back.  ``step_valid[E, steps]`` masks rounds whose sampled max
    batch count is below the global grid height.  Fixed-K only (every
    slot real), so the in-step server-gradient average is the plain mean
    over slots.  The Adam step count of the stacked client optimizer
    stays a single shared scalar (as in the non-participating engine) —
    it advances with the rounds regardless of who was sampled.  Returns
    ``run(stacked_clients[N,...], server, c_opt, s_opt,
    batches[E,S,NB,...], b_idx[E,steps,S], key_idx[E,steps],
    step_valid[E,steps], base_key, slot_gid[E,S])
    -> (..., [E, steps, S] losses)``.
    """
    step, keyed = sflv3_step_fn(adapter, opt_client, opt_server, k_slots,
                                transport, privacy)

    def rowwise(tree):
        """Leaves with a leading global-hospital axis (vs shared scalars
        like the Adam count)."""
        return jax.tree.map(
            lambda x: np.ndim(x) > 0 and np.shape(x)[0] == n_global, tree)

    def gather(tree, gid):
        return jax.tree.map(
            lambda x, r: x[gid] if r else x, tree, rowwise(tree))

    def scatter(full, rows, gid):
        return jax.tree.map(
            lambda x, y, r: x.at[gid].set(y) if r else y,
            full, rows, rowwise(full))

    def sflv3_run_participation(stacked_clients, server, c_opt, s_opt,
                                batches, b_idx, key_idx, step_valid,
                                base_key, slot_gid):
        def round_body(carry, xs):
            sc, sp, co, so = carry
            b_e, bi_e, ki_e, sv_e, gid_e = xs
            sck = gather(sc, gid_e)
            cok = gather(co, gid_e)

            def body(c2, xs2):
                sck_, sp_, cok_, so_ = c2
                bi, ki, sv = xs2
                batch = jax.tree.map(
                    lambda x: x[jnp.arange(k_slots), bi], b_e)
                out = step(sck_, sp_, cok_, so_, batch,
                           _step_key(base_key, ki, keyed), gid_e)
                new = tree_select(sv > 0, out[:4],
                                  (sck_, sp_, cok_, so_))
                return new, jnp.where(sv > 0, out[4], 0.0)

            (sck, sp, cok, so), losses = jax.lax.scan(
                body, (sck, sp, cok, so), (bi_e, ki_e, sv_e))
            sc = scatter(sc, sck, gid_e)
            co = scatter(co, cok, gid_e)
            if sync_clients:
                with jax.named_scope("update"):
                    m = jax.tree.map(lambda x: x.mean(axis=0), sck)
                    sc = jax.tree.map(
                        lambda x, mm: jnp.broadcast_to(mm[None], x.shape),
                        sc, m)
            return (sc, sp, co, so), losses

        carry, losses = jax.lax.scan(
            round_body, (stacked_clients, server, c_opt, s_opt),
            (batches, b_idx, key_idx, step_valid, slot_gid))
        return (*carry, losses)

    return _donating_jit(sflv3_run_participation,
                         donate_argnums=(0, 1, 2, 3, 4))


# ---------------------------------------------------------------------------
# host-side helpers shared by the strategies' compiled paths
# ---------------------------------------------------------------------------

def client_major_log(losses, packed: PackedEpoch):
    """Flatten a ``[C, NB]`` loss array in client-major valid order —
    exactly the stepwise FL/centralized loss ordering."""
    arr = np.asarray(losses)
    flat, weights = [], []
    for c, nb in enumerate(packed.n_batches):
        flat.extend(float(x) for x in arr[c, :nb])
        weights.extend(packed.step_examples[c])
    return flat, weights


def scheduled_log(losses, sched: np.ndarray, packed: PackedEpoch):
    """Per-step losses already in schedule order; weights follow the
    schedule's (client, batch) rows."""
    arr = np.asarray(losses)
    flat = [float(x) for x in arr]
    weights = [packed.step_examples[int(c)][int(b)] for c, b in sched]
    return flat, weights


def key_index_grid(strategy, packed: PackedEpoch) -> np.ndarray:
    """[C, NB] per-step key indices in client-major stepwise order (FL)."""
    grid = np.zeros((len(packed.n_batches), packed.nb_max), np.uint32)
    if strategy._keyed:
        for c, nb in enumerate(packed.n_batches):
            grid[c, :nb] = strategy._take_key_indices(nb)
    return grid
