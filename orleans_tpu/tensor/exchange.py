"""ShardExchange: on-device cross-shard message routing over the mesh.

The arena is mesh-sharded (the directory's consistent-hash assignment IS
the shard-block map — arena.py, runtime/ring.py), but until now a batch's
scatter into rows owned by OTHER shards was left to XLA's implicit
collectives: every `state.at[rows].set` over a sharded column turns into
unstructured gather/scatter communication, re-planned per kernel.  This
module makes the cross-shard hop an EXPLICIT, structured exchange — the
device analog of the cross-silo slab path (tensor/router.py), so the
8-device mesh runs as one logical cluster with host transport reserved
for true cross-process hops:

1. **bucket** — each shard classifies its slice of the batch by
   destination shard (``rows // shard_capacity``; identical to the
   directory's `shard_of_keys` hash by construction — the agreement is
   property-tested) and packs messages into a ``[n_shards, cap]`` send
   buffer;
2. **exchange** — ONE ``lax.all_to_all`` over the mesh axis moves every
   bucket to its owner (inside the compiled program: the fused window
   threads this through its ``lax.scan``);
3. **fold** — the received lanes carry rows that are all shard-local, so
   the existing step kernel's scatter/segment-sum applies them without
   further communication.

**Occupancy-sized buckets** (the perf contract): ``cap`` is NOT a
worst-case bound.  Every exchange measures the per-destination bucket
demand on device (``need`` — the true lane count wanting each bucket,
overflow included) and a per-(type, method) estimator quantizes the
observed peak onto a small ladder ({2^k} ∪ {3·2^(k-1)}, ≤33% overshoot,
O(log) rungs): caps GROW immediately when demand overflows (the parked
redelivery below is the correctness net while the estimate lags) and
SHRINK only after ``exchange_shrink_patience`` calm drains, to a rung at
most half the grant, so steady traffic never churns compiles.  Demand is
kept per valid lane (``REF_LANES``), so one estimate plans every batch
width a site sees.  A site whose measured demand is zero
plans ``cap == 0`` and the exchange short-circuits to a classification
pass — no sort, no all_to_all, output width == input width — which is
also what a host-side shard-ALIGNED batch (``align_plan``) gets by
construction.  Before measurement lands, ``plan`` falls back to the old
worst-case formula (``pad_quantum`` / ``capacity_factor``), so the first
dispatch is always safe.

Exactness across the bounded buckets: a lane that does not fit its
bucket (``cap`` overflow under skew, or ANY cross lane while the
estimate says 0) is never silently lost — the send side computes a
per-lane ``dropped`` mask, the engine parks it like an optimistic
miss-check, and the dropped lanes re-deliver next tick through the
exact same path with their ORIGINAL ``inject_tick`` stamp (the latency
ledger therefore includes the redelivery wait, same contract as the
miss path).  Inside a fused window the dropped count folds into the
window's miss counter instead: a nonzero count fails ``verify()`` and
the auto-fuser rolls back and replays unfused — transparency never
costs exactness.

Ordering caveat (same as host-batch padding): the exchange permutes lane
order within a (type, method) batch.  Delivery SETS are preserved
exactly; handlers that resolve duplicate-row writes by lane order
(``scatter_rows`` with duplicate destinations) are order-sensitive and
should combine with ``seg_*`` instead — the contract vector_grain.py
already states for fan-in.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec

#: estimator site key: the (type_name, method) a batch executes as —
#: caps are per-site because a source leg and its emit leg can have
#: wildly different cross-shard demand (an aligned injection has none;
#: its fan-in delivery carries the workload's whole cross ratio)
Site = Tuple[str, str]

#: the occupancy estimators keep a site's demand per this many valid
#: lanes of a source shard's slice.  A site's batches come at many
#: widths (a tick merges however many slabs arrived), and the share of
#: a slice's valid lanes bound for each destination is what holds from
#: one width to the next; a grant is scaled to a batch's own valid
#: lanes when it is planned
REF_LANES = 1 << 20


def pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def classify_lanes(rows, mask, shard_capacity: int, L: int, n: int):
    """THE destination-classification algebra, shared by every path
    that asks "which lanes are home?" (the structured per-shard body,
    the cap-0 fast paths, and the disengaged probe): inputs are the
    PADDED global (or per-shard) lanes; returns ``(valid, dest, local,
    cross)``.  ``chunk`` is position // L — identical to the shard_map
    split by construction.  Kept free of reductions so the lean in-scan
    caller pays nothing it did not ask for; demand wants
    ``demand_per_dest`` on top."""
    m_pad = rows.shape[0]
    chunk = jnp.arange(m_pad, dtype=jnp.int32) // L
    valid = mask & (rows >= 0)
    dest = jnp.where(valid, rows // shard_capacity, n)
    local = valid & (dest == chunk)
    cross = valid & ~local
    return valid, dest, local, cross


def demand_per_dest(cross, dest, n: int):
    """Per-destination-shard lane demand (int32[n]) — the occupancy
    estimator's input; a global count when computed outside shard_map
    (an upper bound on the per-(src,dst) bucket need — growth-safe,
    refined by the next measured structured drain)."""
    return jax.ops.segment_sum(
        cross.astype(jnp.int32), jnp.where(cross, dest, n),
        num_segments=n + 1)[:n]


def ladder_ceil(n: int) -> int:
    """Smallest ladder rung ≥ n, rungs {2^k} ∪ {3·2^(k-1)}
    (1, 2, 3, 4, 6, 8, 12, 16, 24, ...): ≤33% overshoot where pow2
    pays up to 100%, still O(log) distinct values so the compile set
    under varying demand stays bounded.  0 maps to 0."""
    n = int(n)
    if n <= 0:
        return 0
    p = pow2ceil(n)
    three = 3 * (p // 4)
    return three if three >= n else p


def _to_ref(v, valid: float) -> np.ndarray:
    """Demand out of ``valid`` lanes of a slice, per ``REF_LANES``."""
    return np.ceil(np.asarray(v, np.float64)
                   * (REF_LANES / max(float(valid), 1.0))).astype(np.int64)


def _scaled(rung: int, valid: float) -> int:
    """A grant kept per ``REF_LANES`` valid lanes, as the ladder rung
    that covers it over a slice of ``valid`` lanes."""
    return ladder_ceil(int(np.ceil(int(rung) * float(valid) / REF_LANES)))


class _SiteEstimator:
    """Measured bucket demand for one (type, method) exchange site.

    Tracks the per-destination-shard demand peak and grants a quantized
    cap: growth is immediate (an undersized grant only costs a parked
    redelivery, but staying undersized would cost one EVERY tick);
    shrink waits for ``patience`` consecutive calm observations and a
    calm rung at most half the grant, so a steady state whose demand
    sits at a rung boundary never flaps compiles.  Demand arrives per
    ``REF_LANES`` valid lanes of a source slice
    (``ShardExchange.observe_need`` scales it), so batches of different
    widths read alike."""

    def __init__(self, n_shards: int) -> None:
        self.n_shards = n_shards
        self.peak = np.zeros(n_shards, np.int64)    # all-time, for gauges
        self._window = np.zeros(n_shards, np.int64)  # since last decision
        self._obs = 0
        self.grant: Optional[int] = None
        self.observations = 0
        # per-DESTINATION formulation: one rung per destination (send
        # segments) + one receive rung over the worst shard's total
        # inbound.  Decided on its own window so the scalar grant's
        # schedule (and the tests pinning it) is untouched.
        self.grants: Optional[np.ndarray] = None    # int64[n] send caps
        self.recv_grant: Optional[int] = None       # inbound rung
        self.peak_inbound = np.zeros(n_shards, np.int64)
        self.last_need = np.zeros(n_shards, np.int64)
        # the largest share of a batch's lanes that were valid, and the
        # most valid lanes a source slice held: what a batch is planned
        # over (ShardExchange._valid_lanes)
        self.fill = 0.0
        self.valid = 0
        self._window_pd = np.zeros(n_shards, np.int64)
        self._window_in = np.zeros(n_shards, np.int64)
        self._obs_pd = 0

    @staticmethod
    def _rungs(vec: np.ndarray, headroom: float) -> np.ndarray:
        """Per-destination rungs; a rung one ladder step below the top
        one takes the top rung.  Under even traffic every destination's
        share straddles the same rung boundary, and noise alone would
        otherwise move a rung (and every plan) long after the first
        observations; a destination far below the top keeps its own."""
        rungs = np.array([ladder_ceil(int(np.ceil(float(v) * headroom)))
                          if v > 0 else 0 for v in vec], np.int64)
        top = rungs.max(initial=0)
        return np.where(3 * rungs >= 2 * top, top, rungs)

    def observe(self, need: np.ndarray, headroom: float,
                patience: int, inbound: Optional[np.ndarray] = None
                ) -> Tuple[bool, bool]:
        """Fold one drained need vector; returns (legacy grant changed,
        per-dest grants changed) — the caller bumps the exchange's plan
        version for the planes the configured mode can actually bake
        (a per-dest-only rung move must NOT re-trace a "never" run).
        ``need`` is the per-destination demand maxed over source shards
        (sizes the per-dest send caps); ``inbound`` is the same demand
        SUMMED over sources — each destination's total inbound, which
        sizes the receive rung.  Legacy [n]-tail drains pass only
        ``need``: it then stands in for the inbound too (exact for
        globally counted tails, an upper bound otherwise)."""
        need = np.asarray(need, np.int64)
        inb = need if inbound is None else np.asarray(inbound, np.int64)
        self.peak = np.maximum(self.peak, need)
        self.peak_inbound = np.maximum(self.peak_inbound, inb)
        self.last_need = need
        self._window = np.maximum(self._window, need)
        self._obs += 1
        self.observations += 1
        changed = False
        want = ladder_ceil(int(np.ceil(float(need.max()) * headroom))) \
            if need.max() > 0 else 0
        if self.grant is None or want > self.grant:
            self.grant = want
            self._window = np.zeros(self.n_shards, np.int64)
            self._obs = 0
            changed = True
        elif self._obs >= max(1, int(patience)):
            calm = ladder_ceil(int(np.ceil(float(self._window.max())
                                           * headroom)))
            self._window = np.zeros(self.n_shards, np.int64)
            self._obs = 0
            if calm < self.grant and 2 * calm <= self.grant:
                self.grant = calm
                changed = True
        # per-destination grants: any rung grows immediately; shrink
        # waits for a full calm window (same discipline, vectorized)
        self._window_pd = np.maximum(self._window_pd, need)
        self._window_in = np.maximum(self._window_in, inb)
        self._obs_pd += 1
        changed_pd = False
        want_pd = self._rungs(need, headroom)
        want_r = ladder_ceil(int(np.ceil(float(inb.max()) * headroom))) \
            if inb.max() > 0 else 0
        if self.grants is None or (want_pd > self.grants).any() \
                or want_r > self.recv_grant:
            self.grants = want_pd if self.grants is None \
                else np.maximum(self.grants, want_pd)
            self.recv_grant = want_r if self.recv_grant is None \
                else max(self.recv_grant, want_r)
            self._window_pd = np.zeros(self.n_shards, np.int64)
            self._window_in = np.zeros(self.n_shards, np.int64)
            self._obs_pd = 0
            changed_pd = True
        elif self._obs_pd >= max(1, int(patience)):
            calm_pd = self._rungs(self._window_pd, headroom)
            calm_r = ladder_ceil(int(np.ceil(
                float(self._window_in.max()) * headroom))) \
                if self._window_in.max() > 0 else 0
            self._window_pd = np.zeros(self.n_shards, np.int64)
            self._window_in = np.zeros(self.n_shards, np.int64)
            self._obs_pd = 0
            shrink = (calm_pd < self.grants) & (2 * calm_pd <= self.grants)
            shrink_r = calm_r < self.recv_grant \
                and 2 * calm_r <= self.recv_grant
            if shrink.any() or shrink_r:
                self.grants = np.where(shrink, calm_pd, self.grants)
                if shrink_r:
                    self.recv_grant = calm_r
                changed_pd = True
        return changed, changed_pd

    def snapshot(self) -> Dict[str, Any]:
        return {"grant": self.grant,
                "grants": None if self.grants is None
                else self.grants.tolist(),
                "recv_grant": self.recv_grant,
                "peak_need": self.peak.tolist(),
                "peak_inbound": self.peak_inbound.tolist(),
                "per_valid_lanes": REF_LANES,
                "fill": round(self.fill, 4),
                "valid": self.valid,
                "observations": self.observations}


class ShardExchange:
    """Per-engine exchange plane: builds and caches the jitted exchange
    programs (one per (batch size, cap, shard layout) — batch sizes are
    stable in steady state and cap moves on the quantized ladder only)
    and holds the device-side stat accumulators the engine drains at
    quiescence."""

    def __init__(self, engine) -> None:
        self.engine = engine
        self.mesh = engine.mesh
        self.axis = engine.config.mesh_axis
        self.n_shards = engine.n_shards
        self._platform = str(
            np.asarray(self.mesh.devices).flat[0].platform)
        # disengaged-probe pacing, PER SITE: one measure-only
        # classification per exchange_probe_interval occurrences of
        # each (type, method) — a single global clock would alias with
        # the deterministic per-tick group rotation and could leave a
        # site permanently unsampled
        self._probe_clocks: Dict[Site, int] = {}
        # cumulative stats (folded from device at drain points)
        self.exchanges_run = 0
        self.cross_shard_msgs = 0
        self.delivered_msgs = 0
        self.dropped_msgs = 0
        self.redeliveries = 0
        self.exchange_seconds = 0.0
        # bucket utilization: logical input lanes vs the padded output
        # lanes every downstream kernel pays for — THE number the
        # occupancy sizing moves (worst-case caps ran this at ~0.12)
        self.live_lanes = 0
        self.padded_lanes = 0
        # overlap: wall time pre-dispatched exchanges spent running
        # under other work before their consuming group needed them
        self.overlap_seconds = 0.0
        self.overlap_hits = 0
        # pre-dispatched exchanges that went stale before consumption
        # (their counters were never folded — the inline recompute's
        # were, so dispatch telemetry counts each logical batch once)
        self.pre_discards = 0
        # occupancy-sized caps: per-site estimators + a version the
        # fused plan signature watches (any grant move re-traces, cause
        # bucket_growth — never a silent per-tick recompile)
        self.estimators: Dict[Site, _SiteEstimator] = {}
        self.cap_version = 0
        self._jit_cache: Dict[Tuple[int, int, int, int], Any] = {}
        #: global widths THIS plane produced (exchange outputs, aligned
        #: layouts): only these keep their exact per-shard split in
        #: plan() — an organic batch that merely happens to be
        #: n-divisible still quantizes onto the ladder, so the compile
        #: set stays O(log) under drifting sizes.  Bounded: derived
        #: from ladder L x ladder cap combinations.
        self._transport_widths: set = set()
        #: shapes already compiled with SOME cap — a new cap for a seen
        #: (L, shard_capacity, leaves) is a re-quantization, recorded
        self._seen_shapes: Dict[Tuple[int, int, int], set] = {}
        # trace capture: fused builds drain this to account in-window
        # exchange shapes for the utilization counters
        self.trace_log: List[Tuple[Site, int, int]] = []

    def adopt_stats(self, prev: "Optional[ShardExchange]") -> None:
        """Carry cumulative counters AND the demand estimators across a
        mesh reshard when the shard count is unchanged (the engine
        rebuilds the exchange; the perf trajectory must not reset).  A
        reshard to a DIFFERENT shard count invalidates the per-dest
        vectors — estimators restart from the safe fallback plan."""
        if prev is None:
            return
        self.exchanges_run = prev.exchanges_run
        self.cross_shard_msgs = prev.cross_shard_msgs
        self.delivered_msgs = prev.delivered_msgs
        self.dropped_msgs = prev.dropped_msgs
        self.redeliveries = prev.redeliveries
        self.exchange_seconds = prev.exchange_seconds
        self.live_lanes = prev.live_lanes
        self.padded_lanes = prev.padded_lanes
        self.overlap_seconds = prev.overlap_seconds
        self.overlap_hits = prev.overlap_hits
        self.pre_discards = prev.pre_discards
        self.cap_version = prev.cap_version + 1
        if prev.n_shards == self.n_shards:
            self.estimators = prev.estimators
            self._transport_widths = set(prev._transport_widths)

    # -- engagement (structured vs identity) --------------------------------

    def engaged(self) -> bool:
        """Whether the STRUCTURED formulation (bucket + all_to_all)
        runs at all.  "auto" engages it only over a real accelerator
        interconnect: on a host-virtual mesh every collective is a
        synchronized memcpy inside one process, so the structured
        region costs strictly more than the implicit-collective
        scatter it replaces (measured at every width — the multichip
        bench's exchange_attribution).  Disengaged, the exchange is
        IDENTITY: delivery rides the same implicit collectives as
        exchange-off (unconditionally exact), and the sampled probe
        keeps the demand estimators + cross-traffic counters honest."""
        mode = getattr(self.engine.config, "exchange_structured", "auto")
        if mode == "always":
            return True
        if mode == "never":
            return False
        return self._platform != "cpu"

    def note_transport_width(self, w: int) -> None:
        """Register a global width this plane produced (exchange output
        or aligned layout) — plan() keeps such widths' exact per-shard
        split instead of re-quantizing them."""
        self._transport_widths.add(int(w))

    def probe_scale(self, site: Site, interval: int) -> int:
        """Advance the site's probe clock; 0 = this occurrence is not
        probed, otherwise the SAMPLING SCALE for the measure-only
        classification — the number of occurrences (inclusive) the
        probe stands in for, so every occurrence is covered by exactly
        one probe's scale window and the folded counters stay exact-in-
        expectation even for short runs.  A site's FIRST occurrence
        always probes (scale 1): telemetry and the demand estimate
        exist from the start instead of after interval-1 silent
        groups."""
        pending = self._probe_clocks.get(site)
        if pending is None:
            self._probe_clocks[site] = 0
            return 1
        pending += 1
        if pending >= max(1, interval):
            self._probe_clocks[site] = 0
            return pending
        self._probe_clocks[site] = pending
        return 0

    def _probe(self, arena, rows, mask, site: Site) -> Any:
        """Measure-only classification for a disengaged exchange: one
        async jit returning the int32[3+2n] stats vector (cross, 0,
        valid, per-dest demand twice — the global count is both an
        upper bound on the per-src need and the exact total inbound) —
        the batch itself is untouched and delivers through the normal
        path, so the parked check must never redeliver
        (measure_only)."""
        n = self.n_shards
        shard_capacity = int(arena.shard_capacity)
        L = self.slice_lanes(int(rows.shape[0]))
        key = ("probe", L, shard_capacity)
        fn = self._jit_cache.get(key)
        if fn is None:
            m_pad = n * L

            # named for the device trace, which groups device time by
            # program: this one shows as ``jit__exchange_probe``
            def _exchange_probe(rows, mask):
                def pad(x, fill):
                    if x.shape[0] == m_pad:
                        return x
                    return jnp.pad(x, [(0, m_pad - x.shape[0])],
                                   constant_values=fill)
                rows_p = pad(jnp.asarray(rows, jnp.int32), -1)
                mask_p = pad(jnp.asarray(mask, bool), False)
                valid, dest, _local, cross = classify_lanes(
                    rows_p, mask_p, shard_capacity, L, n)
                # probe semantics: cross lanes DELIVER (through the
                # implicit-collective path) — counted as cross traffic,
                # never as drops
                g = demand_per_dest(cross, dest, n)
                return jnp.concatenate([jnp.stack([
                    jnp.sum(cross.astype(jnp.int32)),
                    jnp.int32(0),
                    jnp.sum(valid.astype(jnp.int32)),
                ]), g, g])
            fn = jax.jit(_exchange_probe)
            self._jit_cache[key] = fn
        return fn(jnp.asarray(rows), mask)

    # -- occupancy-sized planning -------------------------------------------

    def observe_need(self, site: Site, need: np.ndarray,
                     inbound: Optional[np.ndarray] = None, *,
                     valid: int, width: int) -> None:
        """Fold one drained per-destination demand vector for a site,
        measured on a ``width``-lane batch of which ``valid`` lanes
        were valid.  A [2n] vector (max-half ‖ sum-half) may arrive as
        one array in ``need``; it is split here so every drain path can
        stay width-agnostic."""
        cfg = self.engine.config
        need = np.asarray(need)
        n = self.n_shards
        if inbound is None and need.shape[0] == 2 * n:
            need, inbound = need[:n], need[n:]
        est = self.estimators.get(site)
        if est is None:
            est = self.estimators[site] = _SiteEstimator(self.n_shards)
        per_slice = valid / n
        est.fill = max(est.fill, min(1.0, valid / max(int(width), 1)))
        est.valid = max(est.valid, int(np.ceil(per_slice)))
        changed, changed_pd = est.observe(
            _to_ref(need, per_slice), cfg.exchange_headroom,
            cfg.exchange_shrink_patience,
            inbound=None if inbound is None
            else _to_ref(inbound, per_slice))
        # a per-dest-only rung move is invisible to a "never" run's
        # baked plans — bumping the version there would re-trace every
        # fused window for a vector no plan consumes (the estimator
        # keeps tracking either way: gauges + a later mode flip)
        if changed or (changed_pd and getattr(
                cfg, "exchange_per_dest", "auto") != "never"):
            self.cap_version += 1
            rec = self.engine._span_recorder()
            if rec is not None:
                # a grant move is the exchange's re-trace trigger
                # (fused plans re-bake on cap_version): one timeline
                # episode per rung move, annotated with the new caps
                rec.plane_span(
                    "exchange", f"grant growth {site}",
                    site=str(site), cap_version=self.cap_version,
                    grant=int(est.grant or 0),
                    recv_grant=int(est.recv_grant or 0),
                    peak_need=int(np.asarray(need).max(initial=0)))

    def _estimate(self, site: Optional[Site]
                  ) -> "Optional[_SiteEstimator]":
        """A measured site's estimator (None: unmeasured / sizing off)."""
        if site is None or not self.engine.config.exchange_occupancy_sizing:
            return None
        return self.estimators.get(site)

    def slice_lanes(self, m: int) -> int:
        """Lanes per shard slice L of an m-lane batch.  A width THIS
        plane produced keeps its exact per-shard split: it is a
        transport shape (the n·W output of an upstream exchange) or an
        aligned layout (n·La) — re-quantizing would shift every lane out
        of its home chunk and re-cross traffic that is already placed.
        Such widths are static per window / key set AND registered
        (`_transport_widths`), so they carry no compile-churn pressure;
        every other size — including organic batches that merely happen
        to be n-divisible — quantizes onto the ladder, keeping the
        compile set O(log) under drifting population."""
        n = self.n_shards
        return m // n if m in self._transport_widths and m % n == 0 \
            else ladder_ceil(-(-m // n))

    def _valid_lanes(self, est: "_SiteEstimator", m: int) -> float:
        """Valid lanes a source slice of an m-lane batch is planned
        over.  A batch built from grain calls is valid throughout, so
        its slices hold m/n (the site's fill is 1); an upstream
        exchange's padded output holds about as many valid lanes a
        slice whatever its width, the most the site has seen."""
        per_slice = m / self.n_shards
        return max(est.fill * per_slice, min(float(est.valid), per_slice))

    def grant_for(self, site: Optional[Site], m: int) -> Optional[int]:
        """A measured site's scalar cap for an m-lane batch."""
        est = self._estimate(site)
        return None if est is None or est.grant is None \
            else _scaled(est.grant, self._valid_lanes(est, m))

    def grants_for(self, site: Optional[Site], m: int
                   ) -> Optional[Tuple[np.ndarray, int]]:
        """The per-destination grant vector + receive rung for an
        m-lane batch of a measured site, or None (unmeasured / sizing
        off)."""
        est = self._estimate(site)
        if est is None or est.grants is None:
            return None
        valid = self._valid_lanes(est, m)
        return (np.array([_scaled(g, valid) for g in est.grants],
                         np.int64),
                _scaled(est.recv_grant or 0, valid))

    def plan(self, m: int, site: Optional[Site] = None
             ) -> Tuple[int, int]:
        """(per-shard lanes L, per-(src,dst) bucket cap) for an m-lane
        batch.  Both ladder-quantized so the compile set under varying
        batch sizes/demand is O(log n); cap is clamped to L (a bucket
        can never need more than one shard's whole slice).  A site with
        a measured grant uses it; an unmeasured site falls back to the
        worst-case formula (``pad_quantum`` floor × ``capacity_factor``
        skew allowance) so the first dispatch never drops avoidably.
        (Host-ALIGNED batches never reach plan(): the fused build skips
        the exchange for them entirely — fused.py `_apply_group`.)"""
        n = self.n_shards
        cfg = self.engine.config
        L = self.slice_lanes(m)
        grant = self.grant_for(site, m)
        if grant is not None:
            return L, min(L, grant)
        cap = min(L, pow2ceil(max(
            int(cfg.exchange_pad_quantum),
            int(L / n * cfg.exchange_capacity_factor))))
        return L, cap

    def plan_ex(self, m: int, site: Optional[Site] = None):
        """The mode-selecting plan: ``("legacy", L, cap, None)`` or
        ``("perdest", L, cap, (caps_tuple, R))``.  The per-destination
        formulation replaces the ``n·cap`` send/receive layout with
        per-dest send segments (width ``sum(caps)``) and one receive
        rung ``R`` sized by the worst shard's total inbound —
        ``exchange_per_dest="auto"`` engages it only when that is
        strictly narrower than the legacy layout, for the measured
        grants or, at an unmeasured site, for the even layout the
        fallback cap implies."""
        L, cap = self.plan(m, site=site)
        cfg = self.engine.config
        mode = getattr(cfg, "exchange_per_dest", "auto")
        if mode == "never":
            return ("legacy", L, cap, None)
        n = self.n_shards
        pd = self.grants_for(site, m)
        if pd is None:
            if mode != "auto" or not cfg.exchange_occupancy_sizing:
                return ("legacy", L, cap, None)
            # unmeasured: the fallback's even layout, every destination
            # the fallback cap and the receive rung the same skew
            # allowance over an even share of the inbound — the plan an
            # even workload measures, so its first program is its last
            grants = np.full(n, cap, np.int64)
            recv = ladder_ceil(int(np.ceil(
                cfg.exchange_capacity_factor * (n - 1) * L / n)))
        else:
            grants, recv = pd
        caps = np.minimum(grants, L).astype(np.int64)
        if caps.max() == 0 or cap == 0:
            # no measured cross demand: the legacy cap-0 fast path is
            # already the narrowest possible program
            return ("legacy", L, cap, None)
        R = max(1, ladder_ceil(min(int(recv), n * L)))
        S = int(caps.sum())
        if mode != "always" and S + R >= 2 * n * cap:
            return ("legacy", L, cap, None)
        return ("perdest", L, cap,
                (tuple(int(c) for c in caps), R))

    def plan_signature(self, sites) -> Tuple:
        """What a fused window's baked exchange plans depend on: the
        occupancy toggle, the fallback knobs, and the current grant per
        site the window exchanges.  prepare() re-traces when this moves
        (cause ``bucket_growth`` — re-quantization is attributed, never
        a silent recompile)."""
        cfg = self.engine.config
        mode = getattr(cfg, "exchange_per_dest", "auto")

        def sig(s):
            est = self._estimate(s)
            if est is None:
                return (s, None, None)
            # a "never" run bakes only legacy plans: the per-dest
            # vector must not churn its signature
            pd = None if mode == "never" or est.grants is None \
                else (tuple(est.grants.tolist()), est.recv_grant)
            return (s, est.grant, pd)
        return (self.engaged(),
                bool(cfg.exchange_occupancy_sizing),
                mode,
                int(cfg.exchange_pad_quantum),
                float(cfg.exchange_capacity_factor),
                tuple(sig(s) for s in sorted(sites)))

    # -- host-side shard alignment ------------------------------------------

    def align_plan(self, rows_np: np.ndarray, shard_capacity: int,
                   quantum: int = 16) -> Optional[Dict[str, Any]]:
        """Pack a KNOWN row set home-shard-local on the host: lanes are
        permuted so shard s's slice of the padded batch holds only rows
        s owns — the fused build then SKIPS the exchange for this
        source entirely (zero sort, zero all_to_all, zero
        classification; staleness re-traces through the generation/
        epoch discipline before the packing can rot).  Returns None
        when any row is invalid (callers keep the dynamic path).

        ``take`` is the gather map from aligned lane → original lane
        (-1 = padding); per-shard width La is quantized to ``quantum``
        multiples (alignment is static per key set, so there is no
        compile-churn pressure pushing it to pow2 — a tighter pad wins
        downstream width)."""
        rows_np = np.asarray(rows_np)
        if rows_np.ndim != 1 or rows_np.size == 0 or (rows_np < 0).any():
            return None
        n = self.n_shards
        dest = rows_np // int(shard_capacity)
        if (dest >= n).any():
            return None
        counts = np.bincount(dest, minlength=n)
        La = max(quantum, -(-int(counts.max()) // quantum) * quantum)
        take = np.full(n * La, -1, np.int64)
        order = np.argsort(dest, kind="stable")
        off = 0
        for s in range(n):
            lanes = order[off:off + counts[s]]
            take[s * La:s * La + len(lanes)] = lanes
            off += counts[s]
        rows_aligned = np.where(take >= 0, rows_np[np.clip(take, 0, None)],
                                -1).astype(np.int32)
        return {"L": La, "m": int(rows_np.size),
                "take": take.astype(np.int32),
                "rows": rows_aligned}

    # -- the per-shard program (pure jax; traced into jit or a fused scan) ---

    def _traced(self, rows, leaves: List[Any], mask, shard_capacity: int,
                L: int, cap: int):
        """The exchange body at padded size ``n * L``: returns
        ``(recv_rows, recv_leaves, recv_mask, dropped, stats)`` where
        ``dropped`` is a bool[n*L] mask in INPUT lane order (slice back
        to m) and ``stats`` is an int32[3 + n]: (cross_shard, dropped,
        delivered) summed over shards followed by the per-destination
        bucket demand maxed over shards — the estimator's input.

        ``cap == 0`` is the packed fast path: classification only (one
        compare + masks), cross lanes drop into redelivery, and the
        output width equals the input width — an aligned or all-local
        batch pays nothing for having the exchange in its program."""
        n = self.n_shards
        axis = self.axis
        m_pad = n * L
        # output lanes per shard: EXACT — local slice + the received
        # buckets, no rung padding.  A downstream exchange (the emit leg
        # of this batch) sees a global width divisible by n and keeps
        # the per-shard split as-is (plan()'s n-divisible rule), so the
        # re-slice stays aligned with THIS exchange's shard boundaries
        # by construction — the accounting test pins it.
        W = L + n * cap

        def pad_to(x, fill):
            if x.shape[0] == m_pad:
                return x
            widths = [(0, m_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths, constant_values=fill)

        rows = pad_to(jnp.asarray(rows, jnp.int32), -1)
        mask = pad_to(jnp.asarray(mask, bool), False)
        leaves = [pad_to(jnp.asarray(x), 0) for x in leaves]

        if cap == 0:
            # packed fast path, WITHOUT shard_map: a zero-cap site has
            # no buckets and no all_to_all, so the classification is
            # plain elementwise algebra GSPMD partitions natively —
            # on op-count-bound virtual meshes the shard_map wrapper
            # itself is the dominant cost of an empty exchange.  Local
            # lanes deliver in place; any cross lane (the estimate says
            # there are none) drops into redelivery; the demand vector
            # is the GLOBAL per-destination count — an upper bound on
            # the per-(src,dst) bucket demand, so a traffic shift grows
            # the cap at least far enough (the next measured drain
            # refines it downward).
            _valid, dest, local, cross = classify_lanes(
                rows, mask, shard_capacity, L, n)
            # cap-0 semantics: cross lanes DROP into redelivery
            # (stats[1]) — the estimate said there were none.  The
            # demand tail is GLOBAL, so it serves as both halves of the
            # [2n] tail: an upper bound on the per-src need and the
            # exact total inbound.
            g = demand_per_dest(cross, dest, n)
            stats = jnp.concatenate([jnp.stack([
                jnp.int32(0),
                jnp.sum(cross.astype(jnp.int32)),
                jnp.sum(local.astype(jnp.int32)),
            ]), g, g])
            recv_rows = jnp.where(local, rows, -1)
            return recv_rows, leaves, local, cross, stats

        def per_shard(rows_l, mask_l, *leaves_l):
            my = jax.lax.axis_index(axis)
            valid = mask_l & (rows_l >= 0)
            # destination shard straight from the row-block layout — the
            # same function as the directory's shard_of_keys (arena rows
            # are allocated in the key's home block; property-tested)
            dest = jnp.where(valid, rows_l // shard_capacity, n)
            # lanes already home stay IN PLACE (first L output lanes):
            # the all_to_all carries only cross-shard traffic, so its
            # volume — and the bucket pressure `cap` must absorb —
            # scales with the cross-shard ratio, not the batch size
            local = valid & (dest == my)
            cross = valid & ~local
            # per-destination bucket demand (overflow INCLUDED): the
            # occupancy signal the estimator sizes future caps from —
            # here per SOURCE shard (reduced by max outside shard_map)
            need = demand_per_dest(cross, dest, n)
            sdest_in = jnp.where(cross, dest, n)
            order = jnp.argsort(sdest_in)  # ties keep relative order
            sdest = sdest_in[order]
            start = jnp.searchsorted(sdest,
                                     jnp.arange(n, dtype=sdest.dtype))
            pos = jnp.arange(L) - start[jnp.clip(sdest, 0, n - 1)]
            fits = (sdest < n) & (pos < cap)
            # out-of-range slot + mode="drop": invalid/overflow lanes
            # scatter nowhere
            slot = jnp.where(fits, sdest * cap + pos, n * cap)
            send_rows = jnp.full(n * cap, -1, jnp.int32) \
                .at[slot].set(rows_l[order], mode="drop")

            def bucket(leaf):
                s = leaf[order]
                out = jnp.zeros((n * cap,) + s.shape[1:], s.dtype)
                return out.at[slot].set(s, mode="drop")

            send_leaves = [bucket(x) for x in leaves_l]

            def a2a(x):
                r = jax.lax.all_to_all(
                    x.reshape((n, cap) + x.shape[1:]), axis,
                    split_axis=0, concat_axis=0)
                return r.reshape((n * cap,) + x.shape[2:])

            recv_rows = jnp.concatenate(
                [jnp.where(local, rows_l, -1), a2a(send_rows)])
            recv_leaves = [
                jnp.concatenate([x, a2a(s)])
                for x, s in zip(leaves_l, send_leaves)]
            recv_mask = recv_rows >= 0
            # dropped mask back in input lane order
            dropped_sorted = (sdest < n) & (pos >= cap)
            dropped_l = jnp.zeros(L, bool).at[order].set(dropped_sorted)
            n_dropped = jnp.sum(dropped_sorted.astype(jnp.int32))
            stats = jnp.concatenate([jnp.stack([
                jnp.sum(cross.astype(jnp.int32)),
                n_dropped,
                jnp.sum(valid.astype(jnp.int32)) - n_dropped,
            ]), need])[None, :]  # [1, 3 + n]: per-shard, reduced outside
            return (recv_rows, recv_mask, dropped_l, stats, *recv_leaves)

        P = PartitionSpec
        sharded = P(axis)
        out_specs = (sharded, sharded, sharded, sharded) \
            + (sharded,) * len(leaves)
        fn = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(sharded, sharded) + (sharded,) * len(leaves),
            out_specs=out_specs, check_vma=False)
        recv_rows, recv_mask, dropped, stats, *recv_leaves = fn(
            rows, mask, *leaves)
        # counts SUM across shards; the per-dest demand reduces BOTH
        # ways into the [2n] tail — MAX over sources (the per-(src,dst)
        # bucket cap must cover the worst src) and SUM over sources
        # (each destination's total inbound, sizing the per-dest
        # formulation's receive rung)
        stats = jnp.concatenate([jnp.sum(stats[:, :3], axis=0),
                                 jnp.max(stats[:, 3:], axis=0),
                                 jnp.sum(stats[:, 3:], axis=0)])
        return recv_rows, recv_leaves, recv_mask, dropped, stats

    def _traced_perdest(self, rows, leaves: List[Any], mask,
                        shard_capacity: int, L: int,
                        caps: Tuple[int, ...], R: int):
        """The per-DESTINATION exchange body: same contract as
        ``_traced`` (``(recv_rows, recv_leaves, recv_mask, dropped,
        stats[3+2n])``), different layout.  Each shard packs its cross
        lanes into per-destination send segments at static offsets
        (width ``S = sum(caps)`` instead of ``n * cap`` — one hot
        destination no longer sizes every lane's buckets), the segments
        move with one ``all_gather`` alongside an ``[n, n]`` fill
        matrix, and each shard compacts its inbound lanes to the single
        receive rung ``R`` with a searchsorted over the fill prefix
        sums + one gather per leaf (no sort).  Receive overflow (total
        inbound past ``R``) is computed on the SENDER from the same
        fill prefix ranks the receiver takes lanes in, so an overflow
        lane parks into the standard redelivery net instead of being
        silently truncated."""
        n = self.n_shards
        axis = self.axis
        m_pad = n * L
        caps_arr = np.asarray(caps, np.int32)
        offs_arr = np.concatenate([[0], np.cumsum(caps_arr)[:-1]]) \
            .astype(np.int32)
        S = int(caps_arr.sum())

        def pad_to(x, fill):
            if x.shape[0] == m_pad:
                return x
            widths = [(0, m_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths, constant_values=fill)

        rows = pad_to(jnp.asarray(rows, jnp.int32), -1)
        mask = pad_to(jnp.asarray(mask, bool), False)
        leaves = [pad_to(jnp.asarray(x), 0) for x in leaves]

        def per_shard(rows_l, mask_l, *leaves_l):
            my = jax.lax.axis_index(axis)
            valid = mask_l & (rows_l >= 0)
            dest = jnp.where(valid, rows_l // shard_capacity, n)
            local = valid & (dest == my)
            cross = valid & ~local
            need = demand_per_dest(cross, dest, n)
            sdest_in = jnp.where(cross, dest, n)
            order = jnp.argsort(sdest_in)  # ties keep relative order
            sdest = sdest_in[order]
            start = jnp.searchsorted(sdest,
                                     jnp.arange(n, dtype=sdest.dtype))
            pos = jnp.arange(L) - start[jnp.clip(sdest, 0, n - 1)]
            caps_v = jnp.asarray(caps_arr)
            offs_v = jnp.asarray(offs_arr)
            sdest_c = jnp.clip(sdest, 0, n - 1)
            fits = (sdest < n) & (pos < caps_v[sdest_c])
            slot = jnp.where(fits, offs_v[sdest_c] + pos, S)
            send_rows = jnp.full(S, -1, jnp.int32) \
                .at[slot].set(rows_l[order], mode="drop")

            def segment(leaf):
                s = leaf[order]
                out = jnp.zeros((S,) + s.shape[1:], s.dtype)
                return out.at[slot].set(s, mode="drop")

            send_leaves = [segment(x) for x in leaves_l]
            # fill matrix: lanes each source actually packed per dest
            fills_row = jnp.minimum(need, caps_v)
            fills = jax.lax.all_gather(fills_row, axis)      # [n, n]
            g_rows = jax.lax.all_gather(send_rows, axis)     # [n, S]
            g_leaves = [jax.lax.all_gather(s, axis)
                        for s in send_leaves]
            # receive compaction to R lanes, src-major order: output
            # position j maps through the inbound prefix sums to
            # (source shard, offset within its segment for me)
            mine = fills[:, my]
            cum = jnp.cumsum(mine)
            total_in = cum[n - 1]
            j = jnp.arange(R)
            src = jnp.searchsorted(cum, j, side="right")
            src_c = jnp.clip(src, 0, n - 1)
            within = j - (cum[src_c] - mine[src_c])
            live = j < total_in
            lane = jnp.clip(offs_v[my] + within, 0, max(S - 1, 0))
            recv_rows_x = jnp.where(live, g_rows[src_c, lane], -1)

            def compact(g):
                out = g[src_c, lane]
                shape = (R,) + (1,) * (out.ndim - 1)
                return jnp.where(live.reshape(shape), out,
                                 jnp.zeros((), out.dtype))

            recv_leaves_x = [compact(g) for g in g_leaves]
            # sender-side receive-overflow: the global rank of a sent
            # lane in the receiver's src-major take order
            before = jnp.cumsum(fills, axis=0) - fills       # excl. src
            rank = before[my][sdest_c] + pos
            recv_drop = fits & (rank >= R)
            dropped_sorted = ((sdest < n) & ~fits) | recv_drop
            dropped_l = jnp.zeros(L, bool).at[order].set(dropped_sorted)
            n_dropped = jnp.sum(dropped_sorted.astype(jnp.int32))
            recv_rows = jnp.concatenate(
                [jnp.where(local, rows_l, -1), recv_rows_x])
            recv_leaves = [
                jnp.concatenate([x, rx])
                for x, rx in zip(leaves_l, recv_leaves_x)]
            recv_mask = recv_rows >= 0
            stats = jnp.concatenate([jnp.stack([
                jnp.sum(cross.astype(jnp.int32)),
                n_dropped,
                jnp.sum(valid.astype(jnp.int32)) - n_dropped,
            ]), need])[None, :]  # [1, 3 + n]: reduced outside
            return (recv_rows, recv_mask, dropped_l, stats, *recv_leaves)

        P = PartitionSpec
        sharded = P(axis)
        out_specs = (sharded, sharded, sharded, sharded) \
            + (sharded,) * len(leaves)
        fn = jax.shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(sharded, sharded) + (sharded,) * len(leaves),
            out_specs=out_specs, check_vma=False)
        recv_rows, recv_mask, dropped, stats, *recv_leaves = fn(
            rows, mask, *leaves)
        stats = jnp.concatenate([jnp.sum(stats[:, :3], axis=0),
                                 jnp.max(stats[:, 3:], axis=0),
                                 jnp.sum(stats[:, 3:], axis=0)])
        return recv_rows, recv_leaves, recv_mask, dropped, stats

    # -- fused-path entry (called under an active trace) ---------------------

    def apply_traced(self, site: Site, shard_capacity: int, rows, args: Any,
                     mask):
        """Exchange inside a fused window trace: returns
        ``(rows2, args2, mask2, dropped_count, need)`` — the dropped
        count folds into the window's device-side miss counter so a
        capacity overflow fails ``verify()`` (rollback + unfused replay)
        instead of losing lanes, and ``need`` (int32[2n + 1]: per-dest
        demand maxed over sources ‖ summed over sources ‖ the batch's
        valid lanes) rides the window's xneed accumulator so steady
        fused traffic keeps the site's occupancy estimate honest in BOTH
        directions.  A group
        whose args are not lane-aligned (slab-style handlers consuming a
        whole buffer per tick, e.g. the twitter dispatcher) passes
        through untouched — permuting rows away from such args would
        break the handler's row↔buffer correspondence."""
        m = rows.shape[0]
        n = self.n_shards
        if not exchangeable_args(args, m):
            return rows, args, mask, jnp.int32(0), \
                jnp.zeros(2 * n + 1, jnp.int32)
        mode, L, cap, pd = self.plan_ex(m, site=site)
        if cap == 0:
            # LEAN in-scan fast path: classification + the miss count,
            # nothing else — the per-tick demand reductions of the full
            # stats vector are cross-device collectives inside the
            # scan, measured as the entire residual cost of an empty
            # exchange on op-count-bound meshes.  A traffic shift here
            # fails verify() (dropped ≠ 0), the rollback's unfused
            # replay re-delivers, and ITS drained stats grow the cap —
            # the estimator's slow feedback half; the fused fast path
            # never pays for a possibility that isn't happening.
            m_pad = n * L

            def pad(x, fill):
                if x.shape[0] == m_pad:
                    return x
                widths = [(0, m_pad - x.shape[0])] + \
                    [(0, 0)] * (x.ndim - 1)
                return jnp.pad(x, widths, constant_values=fill)

            rows_p = pad(jnp.asarray(rows, jnp.int32), -1)
            mask_p = pad(jnp.asarray(mask, bool), False)
            args_p = jax.tree_util.tree_map(
                lambda a: a if jnp.ndim(a) == 0
                else pad(jnp.asarray(a), 0), args)
            _valid, _dest, local, cross = classify_lanes(
                rows_p, mask_p, shard_capacity, L, n)
            dropped = jnp.sum(cross.astype(jnp.int32))
            self.trace_log.append((site, int(m), m_pad))
            self.note_transport_width(m_pad)
            return (jnp.where(local, rows_p, -1), args_p, local,
                    dropped, jnp.zeros(2 * n + 1, jnp.int32))
        leaves, treedef, scalar_ix = _split_leaves(args, m)
        if mode == "perdest":
            caps, R = pd
            rows2, leaves2, mask2, _dropped, stats = self._traced_perdest(
                rows, leaves, mask, shard_capacity, L, caps, R)
        else:
            rows2, leaves2, mask2, _dropped, stats = self._traced(
                rows, leaves, mask, shard_capacity, L, cap)
        args2 = _join_leaves(treedef, scalar_ix, leaves2)
        self.trace_log.append((site, int(m), int(rows2.shape[0])))
        self.note_transport_width(int(rows2.shape[0]))
        return rows2, args2, mask2, stats[1], \
            jnp.concatenate([stats[3:], (stats[1] + stats[2])[None]])

    # -- unfused-path entry (jitted dispatch; stats parked on device) --------

    def dispatch(self, arena, rows, args: Any, mask,
                 site: Optional[Site] = None,
                 defer_stats: bool = False):
        """One async exchange dispatch for an unfused batch.  Returns
        ``(rows2, args2, mask2, dropped_mask, stats)`` with the dropped
        mask and the int32[3+2n] stats still ON DEVICE — the engine parks
        them (like a miss-check) and reads everything in one batched
        transfer at the next quiescence point.  ``defer_stats`` (the
        round-start pre-dispatch) appends a run-cost tuple to the
        return and folds NO counters — the consumer calls
        ``fold_dispatch`` on use or drops the result (stale), so a
        logical batch counts exactly once either way."""
        t0 = time.perf_counter()
        m = int(rows.shape[0])
        shard_capacity = int(arena.shard_capacity)
        mode, L, cap, pd = self.plan_ex(m, site=site)
        leaves, treedef, scalar_ix = _split_leaves(args, m)
        if mode == "perdest":
            caps, R = pd
            key = (L, ("pd", caps, R), shard_capacity, len(leaves))
            cap_label = f"pd{sum(caps)}r{R}"
        else:
            key = (L, cap, shard_capacity, len(leaves))
            cap_label = str(cap)
        fn = self._jit_cache.get(key)
        if fn is None:
            # both cap layouts are one program name in the device trace,
            # ``jit__exchange_kernel``: send, all_to_all, receive
            if mode == "perdest":
                def _exchange_kernel(rows, mask, *leaves):
                    return self._traced_perdest(
                        rows, list(leaves), mask, shard_capacity,
                        L, caps, R)
            else:
                def _exchange_kernel(rows, mask, *leaves):
                    return self._traced(rows, list(leaves), mask,
                                        shard_capacity, L, cap)
            fn = jax.jit(_exchange_kernel)
            self._jit_cache[key] = fn
            shape = (L, shard_capacity, len(leaves))
            seen = self._seen_shapes.setdefault(shape, set())
            if seen:
                # same batch shape, new cap: the occupancy estimate
                # re-quantized the bucket — attribute the recompile
                # (tensor/profiler.py churn cause list) so a flapping
                # estimate can never hide as organic shape churn
                from orleans_tpu.tensor.profiler import CAUSE_BUCKET_GROWTH
                self.engine.compile_tracker.record(
                    CAUSE_BUCKET_GROWTH,
                    key=f"exchange[{L}]cap{sorted(seen)[-1]}"
                        f"->{cap_label}",
                    tick=self.engine.tick_number)
            seen.add(cap_label)
        rows2, leaves2, mask2, dropped, stats = fn(
            jnp.asarray(rows), mask, *leaves)
        args2 = _join_leaves(treedef, scalar_ix, leaves2)
        self.note_transport_width(int(rows2.shape[0]))
        if defer_stats:
            # pre-dispatch path: the consumer folds the run counters
            # (or discards them with the result — a stale pre-exchange
            # must not double-count the inline recompute's batch)
            return rows2, args2, mask2, dropped[:m], stats, \
                (m, int(rows2.shape[0]), time.perf_counter() - t0)
        self.exchanges_run += 1
        self.live_lanes += m
        self.padded_lanes += int(rows2.shape[0])
        self.exchange_seconds += time.perf_counter() - t0
        return rows2, args2, mask2, dropped[:m], stats

    def fold_dispatch(self, run_cost: Tuple[int, int, float]) -> None:
        """Fold a deferred pre-dispatch's run counters at consumption
        (see ``dispatch(defer_stats=True)``)."""
        m, padded, dt = run_cost
        self.exchanges_run += 1
        self.live_lanes += m
        self.padded_lanes += padded
        self.exchange_seconds += dt

    def fold_stats(self, stats_host: np.ndarray,
                   site: Optional[Site] = None,
                   scale: int = 1, width: int = 0) -> None:
        """Accumulate one drained [3 + n] or [3 + 2n] stats vector; the
        demand tail feeds the site's occupancy estimator (a [2n] tail
        splits into max-half ‖ sum-half inside ``observe_need``).
        ``scale > 1`` marks a
        SAMPLED disengaged-mode probe (1-in-scale groups measured):
        count stats multiply up to stay an unbiased estimate comparable
        with engaged-mode exact totals; the demand tail is a peak, not
        a sum, and never scales: it is read per valid lane (the dropped
        plus the delivered ones) of the ``width``-lane batch."""
        self.cross_shard_msgs += int(stats_host[0]) * scale
        self.dropped_msgs += int(stats_host[1]) * scale
        self.delivered_msgs += int(stats_host[2]) * scale
        if site is not None and len(stats_host) > 3:
            self.observe_need(site, np.asarray(stats_host[3:]),
                              valid=int(stats_host[1]) + int(stats_host[2]),
                              width=width)

    def fold_fused_shapes(self, shapes, n_ticks: int) -> None:
        """Account a fused window run's in-window exchanges (shapes were
        captured at trace time): utilization + run counters, no device
        traffic."""
        for _site, m_in, m_out in shapes:
            self.exchanges_run += n_ticks
            self.live_lanes += m_in * n_ticks
            self.padded_lanes += m_out * n_ticks

    def note_overlap(self, seconds: float) -> None:
        self.overlap_seconds += max(0.0, seconds)
        self.overlap_hits += 1

    def utilization(self) -> float:
        """Live input lanes over padded output lanes — how much of the
        width every post-exchange kernel pays for is real traffic."""
        return self.live_lanes / self.padded_lanes \
            if self.padded_lanes else 1.0

    def cap_gauges(self) -> Dict[int, int]:
        """Per-destination-shard occupancy-sized cap (the ladder rung
        the measured peak demand for that shard quantizes to, maxed
        over sites) — the ``route.exchange_cap{shard}`` gauge."""
        cfg = self.engine.config
        out = {s: 0 for s in range(self.n_shards)}
        for est in self.estimators.values():
            for s in range(self.n_shards):
                rung = ladder_ceil(int(np.ceil(
                    float(est.peak[s]) * cfg.exchange_headroom)))
                out[s] = max(out[s], _scaled(rung, est.valid))
        return out

    def cap_util_gauges(self) -> Dict[int, float]:
        """Steady-state utilization of the per-destination grants: the
        LAST drained demand over the current grant per destination,
        maxed over sites — the ``route.exchange_cap_util{shard}``
        gauge.  1.0 means the grant is exactly full; a persistently
        low column is padding every lane pays for."""
        out = {s: 0.0 for s in range(self.n_shards)}
        for est in self.estimators.values():
            if est.grants is None:
                continue
            for s in range(self.n_shards):
                if est.grants[s] > 0:
                    util = float(est.last_need[s]) / float(est.grants[s])
                    out[s] = max(out[s], round(util, 4))
        return out

    def snapshot(self) -> Dict[str, Any]:
        return {
            "n_shards": self.n_shards,
            "exchanges_run": self.exchanges_run,
            "cross_shard_msgs": self.cross_shard_msgs,
            "delivered_msgs": self.delivered_msgs,
            "dropped_msgs": self.dropped_msgs,
            "redeliveries": self.redeliveries,
            "exchange_seconds": round(self.exchange_seconds, 6),
            "compiled_programs": len(self._jit_cache),
            "bucket_utilization": round(self.utilization(), 4),
            "overlap_seconds": round(self.overlap_seconds, 6),
            "overlap_hits": self.overlap_hits,
            "pre_discards": self.pre_discards,
            "cap_version": self.cap_version,
            "sites": {f"{t}.{m}": est.snapshot()
                      for (t, m), est in self.estimators.items()},
        }


def exchangeable_args(args: Any, m: int) -> bool:
    """True when every non-scalar arg leaf is lane-aligned ([m, ...]) —
    the precondition for permuting lanes.  Slab-style handlers (args
    consumed as a whole buffer, not per lane) fail this and keep the
    legacy path."""
    return all(np.ndim(leaf) == 0 or np.shape(leaf)[0] == m
               for leaf in jax.tree_util.tree_leaves(args))


def _split_leaves(args: Any, m: int):
    """Flatten an args pytree into (exchangeable [m, ...] leaves,
    treedef, scalar positions).  Scalar leaves broadcast in the kernels
    and are uniform across lanes, so they bypass the exchange."""
    flat, treedef = jax.tree_util.tree_flatten(args)
    leaves: List[Any] = []
    scalar_ix: Dict[int, Any] = {}
    for i, leaf in enumerate(flat):
        if np.ndim(leaf) == 0:
            scalar_ix[i] = leaf
        else:
            if np.shape(leaf)[0] != m:
                raise ValueError(
                    f"exchange: arg leaf {i} has leading dim "
                    f"{np.shape(leaf)[0]}, batch has {m} lanes")
            leaves.append(leaf)
    return leaves, treedef, scalar_ix


def _join_leaves(treedef, scalar_ix: Dict[int, Any],
                 leaves: List[Any]) -> Any:
    flat: List[Any] = []
    it = iter(leaves)
    for i in range(treedef.num_leaves):
        flat.append(scalar_ix[i] if i in scalar_ix else next(it))
    return jax.tree_util.tree_unflatten(treedef, flat)
