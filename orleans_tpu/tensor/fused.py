"""Tick fusion: compile a steady-state message loop into ONE XLA program.

Why this exists (measured, not guessed): at 1M grains a presence tick's
kernels take ~9ms of pure device time, but the per-tick host
orchestration — one jit dispatch per round plus Python queue plumbing —
costs an order of magnitude more.  The dispatcher's job in steady state
is *structurally constant*: the same (type, method) group arrives every
tick, its emits go to the same destination types, and the directory
doesn't change.  That constancy is exactly what XLA wants: trace the
whole tick — source kernel → device-mirror resolve → destination
kernels → registered fan-outs, recursively to the round cap — once, wrap
it in ``lax.scan`` over a stacked window of T ticks, and dispatch ONE
program where the unfused engine dispatched 3-5 per tick.

This is the north star's "batched graph-propagation kernel" taken to its
conclusion (SURVEY §7: the scheduler IS the tick loop; here the tick
loop IS a compiled program).  The reference has no analog — its
dispatcher walks queues per message (Dispatcher.cs:38); fusion is the
payoff for making dispatch data-flow.

Steady-state contract (checked, not assumed):
* the injected key set is fixed for the window (the injector's set);
* every emit destination key resolves in the frozen directory mirror —
  misses are COUNTED on device and surfaced after the window; a nonzero
  count means the window touched cold grains and the caller must fall
  back to the unfused path (which activates them);
* collection/elasticity/persistence do not run inside a window (they
  are between-tick work, same as the unfused engine).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.tensor.exchange import exchangeable_args
from orleans_tpu.tensor.profiler import (
    CAUSE_BUCKET_GROWTH,
    CAUSE_CONFIG_TOGGLE,
    CAUSE_EPOCH_MISMATCH,
    CAUSE_GENERATION_REPACK,
    CAUSE_MESH_RESHARD,
    CAUSE_NEW_WINDOW,
)
from orleans_tpu.tensor.vector_grain import (
    KEY_SENTINEL,
    Batch,
    Emit,
    ones_mask,
    vector_type,
)


def plan_windows(window: int, n_ticks: int):
    """Uniform-window schedule used by the fused load drivers: one window
    shape for the whole run (one compile), ticks rounded UP to whole
    windows.  Returns (window, n_windows, total_ticks)."""
    window = max(1, min(window, n_ticks))
    n_windows = -(-n_ticks // window)
    return window, n_windows, n_windows * window


def _normalize(out):
    if isinstance(out, dict):
        return out, None, ()
    out = tuple(out)
    state = out[0]
    results = out[1] if len(out) > 1 else None
    emits = out[2] if len(out) > 2 else ()
    return state, results, emits


class _Source:
    """One injection pattern of a fused window (a multi-pattern window
    applies several per tick, in a canonical order).

    Two modes.  STATIC (the steady-state autofuse mode): one key set,
    identical every tick — rows resolve once and ride the trace as
    constants.  STACKED (``_Source.stacked``, the journal fold-replay
    mode): a per-tick [T, m] key matrix with a [T, m] presence mask —
    rows resolve host-side into a [T, m] matrix that rides the scan xs
    as ``__rows__``/``__mask__`` leaves; absent lanes carry row -1 and
    mask False, which every handler/exchange path already treats as an
    exact no-op (the same contract as emit-resolution misses)."""

    def __init__(self, engine, type_name: str, method: str,
                 keys: np.ndarray) -> None:
        if vector_type(type_name) is None:
            raise KeyError(f"{type_name!r} is not a @vector_grain type")
        self.type_name = type_name
        self.method = method
        self.arena = engine.arena_for(type_name)
        self.stacked_rows = False
        self.keys = np.asarray(keys, dtype=np.int64)
        self.refresh_rows()

    @classmethod
    def stacked(cls, engine, type_name: str, method: str,
                keys2d: np.ndarray, mask2d: np.ndarray) -> "_Source":
        if vector_type(type_name) is None:
            raise KeyError(f"{type_name!r} is not a @vector_grain type")
        self = cls.__new__(cls)
        self.type_name = type_name
        self.method = method
        self.arena = engine.arena_for(type_name)
        self.stacked_rows = True
        self.keys2d = np.asarray(keys2d, dtype=np.int64)
        self.mask2d = np.asarray(mask2d, dtype=bool)
        self.lanes = int(self.keys2d.shape[1])
        # the flat unique key set (activation + re-resolution domain)
        self.keys = (np.unique(self.keys2d[self.mask2d])
                     if self.mask2d.any()
                     else np.empty(0, dtype=np.int64))
        self.refresh_rows()
        return self

    def refresh_rows(self) -> None:
        """(Re-)resolve keys → rows against the arena's CURRENT layout
        (activates missing keys — may grow the arena, so rollback
        snapshots must come after; the prepare() contract)."""
        if not self.stacked_rows:
            self.rows = jnp.asarray(self.arena.spread_rows_host(
                self.arena.resolve_rows(self.keys)))
            return
        if len(self.keys):
            self.arena.resolve_rows(self.keys)
        flat = self.keys2d.reshape(-1).copy()
        flat[~self.mask2d.reshape(-1)] = -1
        rows, found = self.arena.lookup_rows(flat)
        rows = np.where(found, rows.astype(np.int64), np.int64(-1))
        self.rows2d = rows.reshape(self.keys2d.shape)


class FusedTickProgram:
    """One compiled multi-tick program for one or more stable injection
    patterns.

    Built by ``TensorEngine.fuse_ticks`` (single pattern — ``run`` takes
    one stacked/static pytree pair) or ``FusedTickProgram.multi``
    (several concurrent steady patterns — ``run`` takes LISTS aligned
    with the sources, applied per tick in source order).  Calling
    ``run`` executes T ticks in one dispatch and updates the arenas'
    states; ``misses`` accumulates the device-side count of emit
    destinations that were not in the frozen directory mirror (must be
    0 for the window to be exact — check with ``verify()``)."""

    def __init__(self, engine, type_name: str, method: str,
                 keys: np.ndarray) -> None:
        self.engine = engine
        self.sources = [_Source(engine, type_name, method, keys)]
        self._finish_init()

    @classmethod
    def multi(cls, engine,
              sources: "List[Tuple[str, str, np.ndarray]]"
              ) -> "FusedTickProgram":
        self = cls.__new__(cls)
        self.engine = engine
        self.sources = [_Source(engine, t, m, k) for t, m, k in sources]
        self._finish_init()
        return self

    @classmethod
    def replay(cls, engine,
               sites: "List[Tuple[str, str, np.ndarray, np.ndarray]]"
               ) -> "FusedTickProgram":
        """Stacked-rows window for journal fold-replay: each site is
        (type_name, method, keys2d [T, m], mask2d [T, m]) — a run of T
        consecutive journaled ticks with per-tick key sets, applied in
        site order each tick.  Absent (site, tick) pairs ride with
        mask False / row -1 and are exact no-ops."""
        self = cls.__new__(cls)
        self.engine = engine
        self.sources = [_Source.stacked(engine, t, m, k2, mk)
                        for t, m, k2, mk in sites]
        self._finish_init()
        return self

    def _finish_init(self) -> None:
        self.n_msgs = sum(
            s.lanes if s.stacked_rows else len(s.keys)
            for s in self.sources)
        self._generations: Dict[str, int] = {}
        # eviction epochs of touched arenas at trace time: the window
        # bakes each arena's directory mirror in as trace constants, so
        # rows FREED since the trace (free-list deactivation — no
        # generation bump) would leave emits resolving to dead slots;
        # prepare() re-traces on mismatch, same as a repack
        self._epochs: Dict[str, int] = {}
        self._touched: List[str] = []
        self._compiled: Callable | None = None
        self._totals = None  # device [miss, delivered] since last verify
        # cross-shard exchange occupancy feedback: the per-site
        # per-destination bucket-demand maxima the window accumulated on
        # device ({site: int32[n_shards]}; read with _totals at verify
        # and folded into the exchange's estimators — fused steady
        # traffic keeps the caps honest in both directions)
        self._xneed = None
        self._exchange_sites: List[str] = []
        self._exchange_shapes: List[Tuple] = []
        self._site_keys: Dict[str, Tuple[str, str]] = {}
        self._exchange_plan_sig: "Tuple | None" = None
        # host-side shard alignment plans per source (or None): baked
        # take/rows/mask constants that pack the source batch
        # home-shard-local so its exchange runs the cap-0 fast path
        self._align: List[Any] = [None] * len(self.sources)
        # latency-ledger integration (tensor/ledger.py): when the owning
        # engine's ledger is enabled at BUILD time, the window program
        # threads the [slots, buckets] histogram through its scan and
        # every applied batch accumulates inside the compiled program —
        # zero per-window host work.  Inside a window each tick's
        # messages complete in their own (virtual) tick, so the recorded
        # delta is 0: the fused steady state IS the zero-queue-delay
        # operating point, and wall latency comes from seconds-per-tick.
        self._ledger_on = False
        self._hist_shape: "Tuple[int, int] | None" = None
        # workload attribution (tensor/attribution.py): baked at build
        # time like the ledger — the window threads the per-arena
        # traffic counts + sketch + slot counters through its scan; a
        # live toggle/sketch-layout change re-traces (config_toggle)
        self._attr_on = False
        self._attr_sig: "Tuple | None" = None
        # cross-shard exchange (tensor/exchange.py): baked at build time
        # like the ledger — the window threads the all_to_all through
        # its scan; a live toggle re-traces (cause config_toggle).
        # In-window bucket overflows fold into the miss counter, so a
        # skewed window fails verify() and replays unfused (exactness
        # over throughput, the standing fused contract).
        self._exchange_on = False
        # stream-subscription routes (tensor/streams_plane.py): the
        # live toggle and every route's adjacency layout version are
        # baked at build time; prepare() re-traces on either moving
        self._streams_on = False
        self._stream_sig: "Tuple | None" = None
        # donation (config.donate_state, default on): the window takes
        # the state columns as donated inputs, so XLA double-buffers in
        # place and back-to-back windows pipeline without a host round
        # trip.  Callers that may need to ROLL BACK (the auto-fuser)
        # must take their snapshot as a device COPY BEFORE the first
        # donated run — copy-before-donate (autofuse._run_window); a
        # rolled-back chain then restores the copy and never touches a
        # donated-away buffer.  donate=False is the undonated serial
        # baseline the exactness A/B replays against.  An explicit
        # caller assignment PINS the mode (prepare() then never syncs
        # it back to the live config — manual drivers that snapshot
        # pre-run buffers by reference rely on staying undonated).
        self._donate = self.engine.config.donate_state
        self._donate_pinned = False
        self._built_donate: "bool | None" = None  # mode _build baked
        # compile-churn attribution: engine.reshard bumps this counter,
        # so a post-reshard re-trace names the reshard as its cause
        # instead of the generation bump it also produced
        self._reshard_count = self.engine.reshard_count

    @property
    def donate(self) -> bool:
        return self._donate

    @donate.setter
    def donate(self, value: bool) -> None:
        self._donate = bool(value)
        self._donate_pinned = True

    # -- legacy single-source aliases (manual drivers, tests) ---------------

    @property
    def type_name(self) -> str:
        return self.sources[0].type_name

    @property
    def method(self) -> str:
        return self.sources[0].method

    @property
    def keys(self) -> np.ndarray:
        return self.sources[0].keys

    @property
    def src_arena(self):
        return self.sources[0].arena

    @property
    def src_rows(self):
        return self.sources[0].rows

    @src_rows.setter
    def src_rows(self, value) -> None:
        self.sources[0].rows = value

    def _is_multi(self) -> bool:
        return len(self.sources) > 1

    def _as_lists(self, stacked_args: Any, static_args: Any
                  ) -> Tuple[List[Any], List[Any]]:
        if self._is_multi():
            return list(stacked_args), list(static_args or
                                            [{}] * len(self.sources))
        return [stacked_args], [static_args or {}]

    # -- trace-time recursion over the emit graph ---------------------------

    def _apply_group(self, states: Dict[str, Any], type_name: str,
                     method: str, rows, args, mask, depth: int, hist,
                     attr, xneed, segments=None, host_keys=None,
                     aligned: bool = False):
        """Apply one (type, method) batch and recurse into its emits,
        registered fan-outs, and registered stream-subscription routes
        — the trace-time unrolling of the engine's multi-round tick.
        ``hist`` is the latency-ledger accumulator threaded through the
        window (unchanged when the ledger is off); ``attr`` is the
        workload-attribution SCAN carry (counts + slots — the sketch is
        folded ONCE per window from the counts delta, see ``window``),
        empty when that plane is off.  ``xneed`` is the exchange's
        per-site bucket-demand accumulator ({site: int32[n_shards]},
        max-merged — the occupancy estimator's fused-path feedback).
        ``segments`` marks a pull-mode delivery batch (row-aligned
        offsets — tensor/streams_plane.py); ``host_keys`` is the source
        pattern's host key set (depth-1 sources only), which the stream
        route uses to recognize its bound publish set; ``aligned`` marks
        a source batch the build packed home-shard-local (its exchange
        plans cap 0 — the classification-only fast path)."""
        info = vector_type(type_name)
        handler = info.handlers[method]
        if type_name not in states:
            # discovery pass: arenas are pulled in lazily as the emit
            # graph is walked; the compiled window carries all of them
            states[type_name] = self.engine.arena_for(type_name).state
            self._note_arena(type_name, self.engine.arena_for(type_name))
        n_rows = next(iter(states[type_name].values())).shape[0]
        miss_total = jnp.int32(0)
        xch = self.engine.exchange
        if self._exchange_on and xch is not None and not aligned \
                and xch.engaged():
            # aligned sources SKIP the exchange entirely: the build
            # packed every lane into its home chunk from concrete rows,
            # and any layout move (grow/compact/eviction/reshard) re-
            # traces through prepare()'s generation/epoch discipline
            # before the constants can go stale — an in-scan
            # classification would re-prove a static fact every tick.
            # A DISENGAGED exchange (identity mode — host-virtual mesh)
            # traces nothing at all: the window IS the exchange-off
            # program, and a live engagement flip re-traces through the
            # plan signature.
            arena = self.engine.arena_for(type_name)
            if arena.sharding is not None:
                # cross-shard exchange INSIDE the window: sources and
                # recursed emit deliveries alike arrive shard-local at
                # their kernel; bucket-overflow lanes count as misses
                # (the window is then non-exact and replays unfused —
                # no in-window redelivery path exists by design)
                site = (type_name, method)
                rows, args, mask, dropped, need = xch.apply_traced(
                    site, int(arena.shard_capacity), rows, args, mask)
                miss_total = miss_total + dropped
                skey = f"{type_name}.{method}"
                if skey in xneed:
                    xneed = {**xneed,
                             skey: jnp.maximum(xneed[skey], need)}
                else:  # discovery pass only — window pre-populates
                    xneed = {**xneed, skey: need}
        # named_scope labels the window HLO for jax.profiler deep
        # captures (tensor/profiler.py) — trace-time only
        with jax.named_scope(f"orleans.fused.{type_name}.{method}"):
            state2, _results, emits = _normalize(
                handler(states[type_name],
                        Batch(rows=rows, args=args, mask=mask,
                              segments=segments), n_rows))
        states = {**states, type_name: state2}
        if self._ledger_on:
            # in-window latency ledger: every applied lane lands in
            # bucket 0 (each tick's messages complete in their own
            # virtual tick — delta 0 by construction), so the general
            # one-hot kernel COLLAPSES to one masked count + a scalar
            # add.  Bit-identical to ledger.accumulate at delta 0, and
            # it removes a per-group scatter from every scanned tick
            # (measured as the dominant in-window plane cost on
            # scatter-hostile backends).
            slot = self.engine.ledger.slot_for(type_name, method)
            hist = hist.at[jnp.int32(slot), 0].add(
                jnp.sum(jnp.asarray(mask, jnp.int32)))
        if self._attr_on:
            # in-window workload attribution, counts + slots only: the
            # sketch fold moved OUT of the scan — window() re-derives
            # it once per window from the counts delta (integer adds
            # commute, so the result is bit-identical to per-lane
            # folds at a fraction of the scatter cost).  Pull-mode
            # delivery batches (segments) fold their counts with the
            # same scatter-free cumulative-sum reduction the handler
            # uses.
            from orleans_tpu.tensor import attribution as _attr
            att = self.engine.attribution
            counts = attr["counts"].get(type_name)
            if counts is None:
                # arena discovered mid-trace (discovery pass only — the
                # real window trace receives every touched arena's
                # accumulator as an input)
                counts = att.counts_for(type_name)
            c2, sl2 = _attr.fold_counts(
                counts, attr["slots"],
                jnp.int32(att.slots.slot_for(type_name, method)),
                rows, jnp.asarray(mask, bool), segments=segments)
            attr = {"counts": {**attr["counts"], type_name: c2},
                    "slots": sl2}
        delivered = jnp.int32(0)
        at_cap = depth >= self.engine.config.max_rounds_per_tick

        out_batches: List[Tuple[str, str, Any, Any, Any]] = []
        emits = emits if isinstance(emits, (tuple, list)) else (emits,)
        for e in emits:
            if e is None:
                continue
            if isinstance(e.keys, tuple):
                # wide destination keys ((hi, lo) int32 words) resolve
                # through the wide mirror inside the window too
                ekeys = tuple(
                    k if (hasattr(k, "dtype") and k.dtype == jnp.int32)
                    else jnp.asarray(k, jnp.int32) for k in e.keys)
                m = ekeys[0].shape[0]
            else:
                ekeys = e.keys if (hasattr(e.keys, "dtype")
                                   and e.keys.dtype == jnp.int32) \
                    else jnp.asarray(e.keys, jnp.int32)
                m = ekeys.shape[0]
            emask = e.mask if e.mask is not None else ones_mask(m)
            out_batches.append((e.interface, e.method, ekeys, e.args, emask))

        fan = self.engine._fanouts.get((type_name, method))
        if fan is not None and not at_cap:
            fanout, dst_type, dst_method = fan
            src_keys = self._src_keys_for(type_name, rows)
            dkeys, dargs, dvalid = fanout.expand(src_keys, args, mask)
            n_dropped, _dmask = fanout.take_drop()
            # source lanes whose expansion overflowed the CSR width
            # parked (delivering nothing this round): count them as
            # misses so verify() fails loudly — the rollback's unfused
            # replay then re-delivers them through the engine's
            # park-and-redeliver path (never silent loss)
            miss_total = miss_total + n_dropped
            out_batches.append((dst_type, dst_method, dkeys, dargs, dvalid))
        elif fan is not None and at_cap:
            # a fan-out the cap prevents from running would silently lose
            # deliveries — surface it via the miss counter
            miss_total = miss_total + jnp.sum(
                jnp.asarray(mask, jnp.int32))

        # stream-subscription routes (tensor/streams_plane.py): the
        # stream-ingress method's messages also fan out to the streams'
        # subscribers.  Baked at build time like the ledger (a live
        # config.stream_plane toggle re-traces, cause config_toggle).
        route = self.engine._stream_routes.get((type_name, method)) \
            if self._streams_on else None
        if route is not None and not at_cap:
            dst_arena = self.engine.arena_for(route.type_name)
            self._note_arena(route.type_name, dst_arena)
            pull = route.pull_layout(dst_arena) \
                if host_keys is not None \
                and route._matches_bound(host_keys) else None
            if pull is not None and pull["n_edges"] > 0:
                # pull fast path, inside the scan: one payload gather
                # per edge + the row-aligned segment reduction in the
                # destination handler — the CSR/offsets ride as trace
                # constants, stamped by prepare()'s re-trace predicate
                lane = pull["src_lane"]
                gargs = jax.tree_util.tree_map(
                    lambda a: a if jnp.ndim(a) == 0
                    else jnp.asarray(a)[lane], args)
                if isinstance(gargs, dict) and "src_key" not in gargs:
                    gargs = {**gargs, "src_key": pull["src_key"]}
                emask = jnp.asarray(mask, bool)[lane]
                delivered = delivered + jnp.sum(emask.astype(jnp.int32))
                states, sub_miss, sub_del, hist, attr, xneed = \
                    self._apply_group(
                        states, route.type_name, route.method,
                        pull["rows"], gargs, emask, depth + 1, hist,
                        attr, xneed, segments=pull["offsets"])
                miss_total = miss_total + sub_miss
                delivered = delivered + sub_del
            else:
                # push path in-window: expand to subscriber keys and
                # resolve like any emit; overflowing source lanes fold
                # into the miss counter (rollback + unfused replay
                # redelivers them — the DeviceFanout contract)
                src_keys = self._src_keys_for(type_name, rows)
                dkeys, dargs, dvalid = route.expand(
                    src_keys, args, jnp.asarray(mask, bool))
                n_dropped, _dmask = route.take_drop()
                miss_total = miss_total + n_dropped
                out_batches.append((route.type_name, route.method,
                                    dkeys, dargs, dvalid))
        elif route is not None and at_cap:
            miss_total = miss_total + jnp.sum(
                jnp.asarray(mask, jnp.int32))
        elif not self._streams_on \
                and (type_name, method) in self.engine._stream_routes:
            # the plane is live-DISABLED but a route exists: its
            # deliveries belong to the host-expansion path, which a
            # compiled window cannot run — count every source lane as a
            # miss so verify() fails and the rollback's unfused replay
            # delivers through _run_stream_routes_pre (fusion is
            # effectively off for routed sources while the toggle is
            # off; silently verifying would LOSE every delivery)
            miss_total = miss_total + jnp.sum(
                jnp.asarray(mask, jnp.int32))

        if at_cap:
            # the unfused engine SPILLS round-cap emits to the next tick;
            # a fused window cannot, so count them as misses — verify()
            # then tells the caller this chain is too deep to fuse
            for _, _, _ekeys, _eargs, emask in out_batches:
                miss_total = miss_total + jnp.sum(
                    jnp.asarray(emask, jnp.int32))
            return states, miss_total, delivered, hist, attr, xneed

        for dst_type, dst_method, ekeys, eargs, emask in out_batches:
            dst_arena = self.engine.arena_for(dst_type)
            self._note_arena(dst_type, dst_arena)
            from orleans_tpu.tensor.engine import resolve_rows_on_device
            drows, miss = resolve_rows_on_device(dst_arena, ekeys, emask)
            delivered = delivered + jnp.sum(jnp.asarray(emask, jnp.int32))
            states, sub_miss, sub_del, hist, attr, xneed = \
                self._apply_group(
                    states, dst_type, dst_method, drows, eargs,
                    drows >= 0, depth + 1, hist, attr, xneed)
            miss_total = miss_total + miss + sub_miss
            delivered = delivered + sub_del
        return states, miss_total, delivered, hist, attr, xneed

    def _src_keys_for(self, type_name: str, rows):
        arena = self.engine.arena_for(type_name)
        # key-of-row lookup on device for fan-out expansion
        key_col = jnp.asarray(arena._key_of_row.astype(np.int64)
                              .clip(0, 2**31 - 2).astype(np.int32))
        return key_col[jnp.clip(rows, 0, key_col.shape[0] - 1)]

    def _note_arena(self, name: str, arena) -> None:
        if name not in self._generations:
            self._generations[name] = arena.generation
            self._epochs[name] = arena.eviction_epoch
            self._touched.append(name)

    # -- compile + run -------------------------------------------------------

    def _build(self, example_args_t: Any) -> Callable:
        from orleans_tpu.tensor.ledger import MAX_SLOTS

        examples = example_args_t if self._is_multi() \
            else [example_args_t]
        # latency ledger: bake the decision at build time (a live toggle
        # takes effect on the next re-trace); the hist shape is part of
        # the compiled signature, so prepare() re-traces when it changes
        self._ledger_on = self.engine.ledger.enabled
        self._hist_shape = (MAX_SLOTS, self.engine.ledger.n_buckets)
        # workload attribution: same bake-at-build discipline as the
        # ledger (prepare() re-traces on toggle/sketch-layout change)
        self._attr_on = self.engine.attribution.enabled
        self._attr_sig = self.engine.attribution.build_signature()
        # cross-shard exchange: same bake-at-build discipline
        self._exchange_on = self.engine._exchange_live()
        # packed cross-lanes (tensor/exchange.py): a source whose key
        # set is static for the window's lifetime is PACKED home-shard-
        # local here, on the host, once — its in-scan exchange then
        # plans cap 0 (classification only: no sort, no all_to_all,
        # output width == input width).  Sources feeding a stream route
        # keep their lane order (pull layouts precompute per-edge
        # source lanes against the bound key order).
        self._align = [None] * len(self.sources)
        if self._exchange_on \
                and self.engine.exchange.engaged() \
                and self.engine.config.exchange_align_sources:
            for i, s in enumerate(self.sources):
                arena = self.engine.arena_for(s.type_name)
                if s.stacked_rows \
                        or arena.sharding is None \
                        or (s.type_name, s.method) \
                        in self.engine._stream_routes \
                        or not exchangeable_args(examples[i],
                                                 len(s.keys)):
                    # stacked sources change lanes per tick — there is
                    # no one host packing to bake
                    continue
                plan = self.engine.exchange.align_plan(
                    np.asarray(s.rows), int(arena.shard_capacity))
                if plan is None:
                    continue
                # the aligned layout is a transport width: this
                # source's EMIT batches inherit it, and their exchange
                # must keep the per-shard split exact
                self.engine.exchange.note_transport_width(
                    len(plan["rows"]))
                self._align[i] = {
                    "take": jnp.asarray(
                        np.clip(plan["take"], 0, None).astype(np.int32)),
                    "rows": jnp.asarray(plan["rows"]),
                    "mask": jnp.asarray(plan["take"] >= 0),
                }
        src_rows = [None if s.stacked_rows
                    else (al["rows"] if al is not None else s.rows)
                    for al, s in zip(self._align, self.sources)]
        masks = [None if s.stacked_rows
                 else (al["mask"] if al is not None
                       else ones_mask(len(s.keys)))
                 for al, s in zip(self._align, self.sources)]
        # the discovery/trace examples must match the lane layout the
        # window's gather produces
        examples = [self._align_tree(i, ex, axis=0)
                    for i, ex in enumerate(examples)]
        # stream-subscription routes (tensor/streams_plane.py): bake the
        # live toggle and warm every route's pull layout EAGERLY — a
        # rebuild under the trace would produce trace-local mirrors, so
        # pull_layout refuses to rebuild there and the trace would bake
        # the push path for a pattern the engine runs pulled
        self._streams_on = self.engine._streams_live()
        if self._streams_on:
            for _key, route in self.engine._stream_routes.items():
                route.pull_layout(self.engine.arena_for(route.type_name))
                if route._push_dirty or route._push is None:
                    # warm the push CSR too: an in-trace rebuild would
                    # bump layout_version AFTER the signature below is
                    # captured, and the next prepare() would spuriously
                    # re-trace the whole window a second time
                    route._rebuild_push()
        self._stream_sig = self.engine._stream_routes_signature()

        def apply_all(states, per_source_args, hist, attr, xneed):
            miss_tot = jnp.int32(0)
            del_tot = jnp.int32(0)
            for i, src in enumerate(self.sources):
                args_i = per_source_args[i]
                if src.stacked_rows:
                    # stacked mode: this tick's rows/mask ride the scan
                    # xs as reserved leaves (per-tick key sets); pop
                    # them so the handler sees only its own args
                    args_i = dict(args_i)
                    rows_i = args_i.pop("__rows__")
                    mask_i = args_i.pop("__mask__")
                    hk = None
                else:
                    rows_i, mask_i, hk = src_rows[i], masks[i], src.keys
                states, miss, dd, hist, attr, xneed = self._apply_group(
                    states, src.type_name, src.method, rows_i,
                    args_i, mask_i, depth=1, hist=hist,
                    attr=attr, xneed=xneed, host_keys=hk,
                    aligned=self._align[i] is not None)
                miss_tot = miss_tot + miss
                del_tot = del_tot + dd
            return states, miss_tot, del_tot, hist, attr, xneed

        def reset_discovery() -> None:
            self._generations = {s.type_name: s.arena.generation
                                 for s in self.sources}
            self._epochs = {s.type_name: s.arena.eviction_epoch
                            for s in self.sources}
            self._touched = []
            for s in self.sources:
                if s.type_name not in self._touched:
                    self._touched.append(s.type_name)

        reset_discovery()

        # discovery: abstractly trace ONE tick so the emit graph's
        # destination arenas are known before the scan carry is fixed.
        # Arenas first touched DURING the abstract trace get tracer-backed
        # state columns; recreate those eagerly and re-discover until the
        # emit graph introduces no new arenas (bounded by the round cap).
        # A FRESH closure per iteration: discovery works by side effect
        # (_note_arena), and jax caches traces by function identity — a
        # reused closure would hit the cache and silently skip the trace.
        xch = self.engine.exchange
        while True:
            known = set(self.engine.arenas)
            reset_discovery()
            if xch is not None:
                # the discovery trace walks every exchange site —
                # capture them (and their in/out widths) for the xneed
                # accumulator layout + the utilization counters
                xch.trace_log = []

            def discover(args_per_source):
                states: Dict[str, Any] = {
                    s.type_name: s.arena.state for s in self.sources}
                hist0 = jnp.zeros(self._hist_shape, jnp.int32)
                attr0 = self._scan_attr(self.attr_state_in(
                    [s.type_name for s in self.sources]))
                _states, miss, _d, _h, _a, _x = apply_all(
                    states, args_per_source, hist0, attr0, {})
                return miss

            jax.eval_shape(discover, examples)
            born_in_trace = set(self.engine.arenas) - known
            if not born_in_trace:
                break
            for name in born_in_trace:
                self.engine.arenas.pop(name)
                self.engine.arena_for(name)  # eager, concrete columns
        touched = list(self._touched)
        shapes = list(xch.trace_log) \
            if (self._exchange_on and xch is not None) else []
        self._exchange_shapes = shapes
        self._site_keys: Dict[str, Tuple[str, str]] = {}
        # the narrowest batch each site exchanges in the window: its
        # accumulated valid lanes are read against that width
        self._site_widths: Dict[str, int] = {}
        for site, m_in, _mo in shapes:
            skey = f"{site[0]}.{site[1]}"
            self._site_keys.setdefault(skey, site)
            self._site_widths[skey] = min(
                m_in, self._site_widths.get(skey, m_in))
        self._exchange_sites = list(self._site_keys)
        self._exchange_plan_sig = xch.plan_signature(
            list(self._site_keys.values())) \
            if (self._exchange_on and xch is not None) else None

        def window(states, statics, stackeds, totals_in, hist_in,
                   attr_in, xneed_in):
            scan_attr_in = self._scan_attr(attr_in)
            # packed sources: ONE gather per leaf per window (outside
            # the scan) re-lays the natural-order inputs home-shard-
            # local; the per-tick exchange inside the scan then runs
            # the cap-0 fast path
            statics = [self._align_tree(i, statics[i], axis=0)
                       for i in range(len(self.sources))]
            stackeds = [self._align_tree(i, stackeds[i], axis=1)
                        for i in range(len(self.sources))]

            def one_tick(carry, args_ts):
                states, hist, attr, xneed = carry
                # static leaves (identical every tick) ride OUTSIDE the
                # scan xs: slicing a [T, m] stack per iteration costs
                # real bandwidth; a closed-over [m] array costs nothing
                merged = [{**statics[i], **args_ts[i]}
                          for i in range(len(self.sources))]
                states, miss, delivered, hist, attr, xneed = apply_all(
                    states, merged, hist, attr, xneed)
                return (states, hist, attr, xneed), (miss, delivered)
            (states, hist, attr, xneed), (misses, delivered) = \
                jax.lax.scan(
                    one_tick, (states, hist_in, scan_attr_in, xneed_in),
                    tuple(stackeds))
            if attr_in:
                # sketch fold, ONCE per window: the scan carried only
                # counts + slots; the CMS re-derives from each arena's
                # counts delta (same hashed row buckets, integer adds
                # commute — bit-identical to per-lane folds, at one
                # capacity-sized scatter per window instead of one
                # lane-sized scatter per group per tick)
                from orleans_tpu.tensor import attribution as _attr
                seeds = self.engine.attribution._seed_arr()
                cms_out = {
                    t: _attr.fold_cms_dense(
                        attr_in["cms"][t],
                        attr["counts"].get(t, attr_in["counts"][t])
                        - attr_in["counts"][t], seeds)
                    for t in attr_in["cms"]}
                attr = {"counts": attr["counts"], "cms": cms_out,
                        "slots": attr["slots"]}
            # totals accumulate ON DEVICE across runs: verify() then
            # reads one 2-element buffer no matter how many windows ran
            # (each completion observation measured ~100ms on the
            # pre-PR-1 chip rig, so per-window reads would dominate).
            # The ledger hist, the attribution pytree and the exchange
            # demand maxima likewise stay on device until an explicit
            # snapshot.
            return states, totals_in + jnp.stack(
                [jnp.sum(misses), jnp.sum(delivered)]), hist, attr, xneed

        self._touched = touched
        self._built_donate = self.donate
        return jax.jit(window,
                       donate_argnums=(0,) if self.donate else ())

    def attr_state_in(self, touched: "List[str] | None" = None):
        """The attribution accumulator pytree a window run (or the
        auto-fuser's AOT lower) passes as ``attr_in`` — empty when the
        plane was off at build time, so the signature stays stable."""
        if not self._attr_on:
            return {}
        return self.engine.attribution.device_state_in(
            touched if touched is not None else self._touched)

    def _align_tree(self, i: int, tree: Any, axis: int) -> Any:
        """Gather one source's args into its packed home-shard-local
        lane order (no-op for unaligned sources).  ``axis=0`` for
        natural [m, ...] leaves (statics / single-tick examples),
        ``axis=1`` for stacked [T, m, ...] leaves — a stacked leaf of
        rank 1 is a per-tick scalar and passes through untouched."""
        al = self._align[i]
        if al is None:
            return tree
        take = al["take"]

        def gather(a):
            if jnp.ndim(a) == 0:
                return a
            if axis == 0:
                return jnp.asarray(a)[take]
            if jnp.ndim(a) == 1:
                return a
            return jnp.asarray(a)[:, take]

        return jax.tree_util.tree_map(gather, tree)

    def xneed_state_in(self):
        """The exchange demand accumulator a window run (or the
        auto-fuser's AOT lower) passes as ``xneed_in`` — empty when the
        exchange was off at build time, so the signature stays
        stable."""
        if not self._exchange_on or not self._exchange_sites:
            return {}
        if self._xneed is not None:
            return self._xneed
        # [2n + 1]: per-dest demand maxed over sources ‖ summed over
        # sources (the per-dest formulation's receive-rung signal) ‖
        # the batch's valid lanes — matches apply_traced's need vector;
        # max-merge is correct for every part (each is a per-tick peak)
        n = self.engine.n_shards
        return {k: jnp.zeros(2 * n + 1, jnp.int32)
                for k in self._exchange_sites}

    def _fold_xneed(self) -> None:
        """Read the accumulated per-site bucket demand (one small
        transfer per site, at an existing sync point) into the
        exchange's occupancy estimators — the fused path's half of the
        cap-sizing feedback loop."""
        xn, self._xneed = self._xneed, None
        xch = self.engine.exchange
        if not xn or xch is None:
            return
        n = self.engine.n_shards
        for skey, vec in xn.items():
            site = self._site_keys.get(skey)
            if site is not None:
                vec = np.asarray(vec)
                xch.observe_need(site, vec[:2 * n], valid=int(vec[2 * n]),
                                 width=self._site_widths[skey])

    @staticmethod
    def _scan_attr(attr_in):
        """The slice of the attribution pytree that rides the scan
        carry: counts + slots.  The sketch stays OUTSIDE the scan and
        folds once per window from the counts delta (see window)."""
        if not attr_in:
            return {}
        return {"counts": attr_in["counts"], "slots": attr_in["slots"]}

    def prepare(self, stacked_args: Any, static_args: Any = None) -> None:
        """Re-resolve the source rows and re-trace if any touched arena
        grew/repacked since the trace (the unfused engine's generation
        discipline).  Idempotent; ``run`` calls it first.  Callers that
        snapshot arena state for rollback (the auto-fuser) MUST call
        this BEFORE taking the snapshot: source re-resolution
        auto-activates evicted keys, which can GROW an arena — a
        post-snapshot grow would make the snapshot unrestorable."""
        engine = self.engine
        stackeds, statics = self._as_lists(stacked_args, static_args)
        from orleans_tpu.tensor.ledger import MAX_SLOTS
        # cause-coded re-trace decision (tensor/profiler.py churn
        # cause list): the FIRST matching condition names the cause —
        # reshard outranks the generation bump it also produced
        # donation target: an explicit caller pin wins (manual drivers
        # that snapshot pre-run buffers by reference stay undonated);
        # otherwise the live config decides and a toggle re-traces
        donate_target = self._donate if self._donate_pinned \
            else engine.config.donate_state
        cause = None
        if self._compiled is None:
            cause = CAUSE_NEW_WINDOW
        elif self._reshard_count != engine.reshard_count:
            cause = CAUSE_MESH_RESHARD
        elif any(engine.arena_for(n).generation != g
                 for n, g in self._generations.items()):
            cause = CAUSE_GENERATION_REPACK
        elif any(engine.arena_for(n).eviction_epoch != e
                 for n, e in self._epochs.items()):
            cause = CAUSE_EPOCH_MISMATCH
        elif self._hist_shape != (MAX_SLOTS, engine.ledger.n_buckets) \
                or self._ledger_on != engine.ledger.enabled \
                or self._attr_sig != engine.attribution.build_signature() \
                or self._exchange_on != engine._exchange_live() \
                or self._streams_on != engine._streams_live() \
                or self._stream_sig != engine._stream_routes_signature():
            # stream-plane toggles AND adjacency rebuilds both land
            # here: the window bakes the CSR/offsets as trace
            # constants, so a layout_version bump must re-trace
            cause = CAUSE_CONFIG_TOGGLE
        elif self._built_donate != donate_target:
            # the compiled window baked the other donation mode (live
            # donate_state toggle, or a re-pinned cached program) —
            # re-trace; the step-program twin clears _step_cache for
            # the same reason
            cause = CAUSE_CONFIG_TOGGLE
        elif self._exchange_on and engine.exchange is not None \
                and self._exchange_plan_sig != engine.exchange \
                .plan_signature(list(self._site_keys.values())):
            # an exchange cap re-quantized (the occupancy estimator
            # moved a grant, or the sizing knobs were live-reloaded):
            # the window baked the old bucket widths as trace constants.
            # Re-trace HERE, cause-coded — grants only move at drain/
            # verify boundaries (estimators fold there), so a steady
            # stream can never recompile per tick
            cause = CAUSE_BUCKET_GROWTH
        if cause is not None:
            # fold pending demand observations under the OLD site
            # layout before the rebuild replaces it
            self._fold_xneed()
            self._donate = donate_target
            for s in self.sources:
                s.refresh_rows()
            examples = [
                {**statics[i], **jax.tree_util.tree_map(lambda a: a[0],
                                                        stackeds[i])}
                for i in range(len(self.sources))]
            t_build = time.perf_counter()
            self._compiled = self._build(
                examples if self._is_multi() else examples[0])
            self._reshard_count = engine.reshard_count
            t_built = time.perf_counter() - t_build
            engine.compile_tracker.record(
                cause,
                key="fused:" + "+".join(f"{s.type_name}.{s.method}"
                                        for s in self.sources),
                seconds=t_built,
                tick=engine.tick_number)
            rec = engine._span_recorder()
            if rec is not None:
                # re-trace episodes on the exchange track: the
                # timeline shows WHEN a window re-baked and why
                rec.plane_span("exchange", f"re-trace {cause}",
                               duration=t_built, cause=cause,
                               tick=engine.tick_number,
                               sources=len(self.sources))

    def run(self, stacked_args: Any, static_args: Any = None) -> None:
        """Execute T fused ticks.

        ``stacked_args``: pytree of genuinely per-tick leaves with a
        leading [T, ...] axis (e.g. the tick counter).  ``static_args``:
        leaves identical every tick, passed at their natural [m, ...]
        shape — they are closed over by the scan instead of stacked, so a
        steady payload costs no per-tick slicing bandwidth.  Multi-source
        programs (``FusedTickProgram.multi``) take LISTS of both, aligned
        with ``sources``."""
        engine = self.engine
        stackeds, statics = self._as_lists(stacked_args, static_args)
        leaves = jax.tree_util.tree_leaves(stackeds)
        if not leaves:
            raise ValueError(
                "stacked_args needs at least one [T, ...] leaf (e.g. a "
                "tick counter) — it sets the window length")
        n_ticks = leaves[0].shape[0]
        self.prepare(stacked_args, static_args)
        states = {n: engine.arena_for(n).state for n in self._touched}
        totals_in = self._totals if self._totals is not None \
            else jnp.zeros(2, dtype=jnp.int32)
        new_states, self._totals, hist_out, attr_out, xneed_out = \
            self._compiled(
                states, statics, stackeds, totals_in,
                engine.ledger.device_hist_in(), self.attr_state_in(),
                self.xneed_state_in())
        if self._ledger_on:
            engine.ledger.device_hist_out(hist_out)
        if self._attr_on:
            engine.attribution.device_state_out(attr_out)
        if self._exchange_on:
            self._xneed = xneed_out
            if engine.exchange is not None:
                engine.exchange.fold_fused_shapes(
                    self._exchange_shapes, n_ticks)
        for n in self._touched:
            # double-buffer flip: donated windows consumed the inputs;
            # the outputs are the live columns now (layout validated)
            engine.arena_for(n).adopt_state(new_states[n])
        # the window's on-device totals accumulator doubles as the
        # pipeline's completion FENCE: it is a program output nothing
        # ever donates (it feeds the NEXT window as a plain input), so
        # event-driven completion can block on it while later windows
        # donate the state buffers away
        engine._tick_fence = self._totals
        if not self._donate:
            engine.donation_fallbacks += 1
        engine.tick_number += n_ticks
        engine.ticks_run += n_ticks
        engine.messages_processed += n_ticks * self.n_msgs
        # collection safety: the window advanced the tick clock without
        # routing through the engine's touch path — every row of a fused
        # arena is a live participant, so stamp them all or the idle
        # sweep would evict hot state mid-steady-state
        for n in self._touched:
            arena = engine.arena_for(n)
            arena.last_use_tick[arena._key_of_row >= 0] = engine.tick_number

    def verify(self) -> int:
        """Sync point: total exactness violations across run() calls since
        the last verify — emit misses (cold destinations), fan-out budget
        overflows, and round-cap spills all count.  Nonzero = the window
        was NOT exact; re-run those ticks unfused.  Also folds the
        windows' emit/fan-out delivery counts into the engine's
        messages_processed (run() counts only source injections eagerly —
        delivery counts live on device until this sync).  ONE 2-element
        device read regardless of how many windows ran since the last
        verify (the on-device totals accumulator).  Also folds the
        accumulated exchange bucket demand into the occupancy
        estimators — an in-window bucket overflow both fails the window
        AND grows the cap, so the re-traced window is exact again."""
        self._fold_xneed()
        if self._totals is None:
            return 0
        totals = np.asarray(self._totals)
        self._totals = None
        self.engine.messages_processed += int(totals[1])
        return int(totals[0])
