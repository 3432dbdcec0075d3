"""Auto-fusion: the engine detects its own steady state and compiles it.

Manual fusion (tensor/fused.py) asks the caller to hand the engine a frozen
key set and drive whole windows.  Auto-fusion removes the ceremony: the
loader calls nothing but ``injector.inject(args)`` per tick, and the engine

1. **detects** K consecutive ticks carrying an identical injection
   pattern — same (type, method), same key set (object identity on the
   injector's cached arrays), same arena generation, same args dict with
   a stable static/per-tick split (leaves reused by identity are static);
2. **compiles** the steady tick into a FusedTickProgram and switches to
   window mode: injections buffer their per-tick leaves and every
   ``auto_fusion_window`` ticks execute as ONE device program;
3. **verifies** each window's device-side miss counter and, on a nonzero
   count (a cold destination, fan-out overflow or round-cap spill inside
   the window), **rolls back** the window from a pre-run state snapshot
   and replays its ticks through the exact unfused path — transparency
   never costs exactness;
4. **disengages** on any pattern break (foreign traffic, changed leaf
   identity, ring change), replaying buffered ticks unfused one at a
   time so per-tick application order is preserved.

No reference analog — the reference's dispatcher walks queues per message
(Dispatcher.cs:38); this is the north-star payoff for making dispatch
data-flow (contract: tensor/fused.py).
"""

from __future__ import annotations

import time
import weakref
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _pin_copy(cols):
    """Copy-before-donate: one compiled device-side copy of an arena's
    state columns, taken as the rollback pin BEFORE the first DONATED
    window of a chain runs (the window consumes the live buffers, so a
    by-reference snapshot would be reading donated-away memory at
    rollback time).  One async dispatch — never an eager per-column
    copy, which measured ruinously slow on the pre-PR-1 chip rig."""
    return jax.tree_util.tree_map(jnp.copy, cols)


class _PatternState:
    """Per-(type, method) detection/engagement state of one steady
    injection stream.  A tick's steady state may carry SEVERAL streams
    (an app running presence + chirper at once; aligned cross-silo slab
    arrivals) — the fuser tracks the whole set and compiles ONE window
    program applying every stream per tick, in canonical order."""

    __slots__ = ("key", "sig", "prev_top", "static_keys", "rows",
                 "keys_host", "generation", "epoch", "static_args")

    def __init__(self, key: Tuple[str, str], sig: Tuple,
                 args: Dict[str, Any], b) -> None:
        self.key = key
        self.sig = sig
        self.prev_top = dict(args)
        self.static_keys = set(args)
        self.rows = b.rows
        self.keys_host = b.keys_host
        self.generation = b.generation
        self.epoch = b.epoch
        self.static_args: Dict[str, Any] = {}


class AutoFuser:

    def __init__(self, engine) -> None:
        self.engine = engine
        # detection state: the steady SET of patterns (sorted by
        # (type, method)) plus a composite signature over all of them
        self._sig: Optional[Tuple] = None
        self._count = 0
        self._patterns: List[_PatternState] = []
        self._activation_passes = -1
        # engaged-window state
        self._program = None
        # per tick, one per-tick-leaf dict PER PATTERN (aligned with
        # self._patterns)
        self._buffer: List[List[Dict[str, Any]]] = []
        self._replaying = False
        # verification chain: windows whose device-side miss counters
        # have not been read yet.  One observation per
        # auto_fusion_verify_windows windows amortizes the completion-
        # observation cost (~100ms on the pre-PR-1 chip rig); rollback
        # then spans the whole chain (snapshot refs are free — the
        # programs never donate their state buffers).
        self._unverified: List[List[Dict[str, Any]]] = []
        self._chain_prog = None
        self._chain_snapshot: Optional[Dict[str, Dict]] = None
        self._chain_counters: Optional[Tuple[int, int, int]] = None
        self._chain_generations: Dict[str, int] = {}
        self._chain_epochs: Dict[str, int] = {}
        self._chain_ledger: Optional[Tuple] = None
        self._chain_attr: Optional[Tuple] = None
        # caches / stats
        self._programs: Dict[Tuple, Any] = {}
        self._disabled: Dict[Tuple, int] = {}   # sig → ring version at ban
        # rollback hysteresis: cumulative rollbacks per signature; a
        # pattern that keeps touching cold keys pays snapshot + rollback +
        # replay every window — after auto_fusion_max_rollbacks strikes it
        # is banned like a fuse failure (until ring/generation change)
        self._rollback_counts: Dict[Tuple, int] = {}
        # identity-memoized CONTENT digests of key arrays: the signature
        # must survive a loader recreating its injector (fresh array,
        # same keys), or every reconnect/loader restart would pay the
        # full detection threshold AND a recompile.  The digest hashes
        # the bytes ONCE per array identity; the weakref guards against
        # id() reuse after garbage collection.
        self._digest_cache: Dict[int, Tuple[Any, int]] = {}
        self.windows_run = 0
        self.windows_rolled_back = 0
        self.ticks_fused = 0

    def _keys_digest(self, arr: np.ndarray) -> int:
        key = id(arr)
        ent = self._digest_cache.get(key)
        if ent is not None and ent[0]() is arr:
            # LRU touch: insertion order doubles as recency order
            self._digest_cache[key] = self._digest_cache.pop(key)
            return ent[1]
        digest = hash((len(arr), arr.tobytes()))
        try:
            ref = weakref.ref(arr)
        except TypeError:  # non-weakrefable array subclass: no memo
            return digest
        while len(self._digest_cache) >= 256:
            # evict ONE least-recently-used entry; hot arrays stay memoized
            self._digest_cache.pop(next(iter(self._digest_cache)))
        self._digest_cache[key] = (ref, digest)
        return digest

    # ================= detection ==========================================

    def _reset(self) -> None:
        self._sig = None
        self._count = 0
        self._patterns = []
        self._program = None

    def has_buffer(self) -> bool:
        return bool(self._buffer) or bool(self._unverified)

    def idle_flush(self) -> None:
        """Engine-loop idle path: the producer stopped mid-window — drain
        every buffered tick through the unfused path now.  Detection
        restarts when the pattern resumes (cheaply: the compiled program
        is cached, so re-engagement needs only 2 matching ticks)."""
        self._break()

    def _break(self) -> None:
        """Pattern break: settle the verification chain (it may roll
        back, replaying chained + buffered ticks), then replay any
        remaining buffered ticks — all BEFORE the breaking tick
        executes, preserving per-tick application order."""
        self._settle_chain()
        if self._buffer:
            self._replay_buffer()
        self._reset()

    def _replay_buffer(self) -> None:
        """Synchronously drain the window buffer through the unfused path,
        one engine tick per buffered tick (exact per-tick application
        order).  Newer work already queued on the engine is stashed and
        restored BEHIND the replayed ticks, so ordering holds even when
        the break was foreign traffic arriving mid-window."""
        engine = self.engine
        stash = engine.queues
        engine.queues = defaultdict(list)
        try:
            while self.flush_partial():
                engine.run_tick()
                # replayed ticks may emit follow-on rounds that spill past
                # the round cap — drain them (bounded: a cyclic emit
                # topology must spill to later ticks, as the unfused
                # engine's round cap does, not hang this synchronous loop)
                for _ in range(engine.config.max_rounds_per_tick):
                    if not any(engine.queues.values()):
                        break
                    engine.run_tick()
        finally:
            self._replaying = False
            for k, v in stash.items():
                if v:
                    engine.queues[k].extend(v)

    def _ring_version(self) -> int:
        silo = self.engine.silo
        return silo.ring.version if silo is not None else 0

    def _scan_live(self) -> Optional[List[Tuple]]:
        """Inspect the live queues; return ``[(key, batch, args, psig)]``
        sorted by (type, method) when EVERY live queue carries exactly
        one fusable injection batch, else None."""
        live = sorted((k, v) for k, v in self.engine.queues.items() if v)
        if not live:
            return None
        entries = []
        for key, batches in live:
            if len(batches) != 1:
                return None
            b = batches[0]
            args = b.args
            if (b.future is not None or b.rows is None
                    or b.keys_host is None or b.no_fanout
                    or b.mask is not None or not isinstance(args, dict)):
                return None
            arena = self.engine.arenas.get(key[0])
            if arena is None or b.generation != arena.generation \
                    or b.epoch != arena.eviction_epoch:
                # stale rows (repack OR free-list eviction since
                # resolution): not fusable this tick — the injector
                # revalidates on its next inject and detection resumes
                return None
            psig = (key[0], key[1], self._keys_digest(b.keys_host),
                    b.generation, tuple(sorted(args)))
            entries.append((key, b, args, psig))
        return entries

    def offer(self) -> bool:
        """Called at tick start.  Returns True when the tick's work was
        consumed into the fused window (caller skips the unfused path)."""
        cfg = self.engine.config
        if cfg.auto_fusion_ticks <= 0 or self._replaying:
            return False
        entries = self._scan_live()
        if entries is None:
            self._break()
            return False
        sig = (tuple(e[3] for e in entries), self._ring_version())
        if self._disabled.get(sig) == self._ring_version():
            self._break()
            return False

        def seed() -> None:
            self._sig = sig
            self._count = 1
            self._patterns = [_PatternState(key, psig, args, b)
                              for key, b, args, psig in entries]
            self._activation_passes = self.engine.activation_passes

        if sig != self._sig:
            self._break()
            seed()
            return False
        # same composite signature again: refine every pattern's static
        # split by leaf identity
        shrunk_engaged = False
        for pat, (key, b, args, _psig) in zip(self._patterns, entries):
            new_static = {k for k in pat.static_keys
                          if args[k] is pat.prev_top.get(k)}
            if self._program is not None \
                    and not set(pat.static_args) <= new_static:
                # a leaf that was static at ENGAGE time changed identity
                # mid-window: window[0]'s per-tick stack lacks that leaf,
                # so continuing would silently apply the frozen value to
                # every buffered tick.  Disengage, replay the buffer
                # unfused, and restart detection from this tick.
                shrunk_engaged = True
            pat.static_keys = new_static
            pat.prev_top = dict(args)
        if shrunk_engaged:
            self._break()
            seed()
            return False
        self._count += 1
        threshold = 2 if sig in self._programs else cfg.auto_fusion_ticks
        if self._count < threshold:
            return False
        if self.engine._pending_checks:
            # outstanding optimistic miss-checks may still activate cold
            # destinations — settle them BEFORE freezing a directory
            # mirror, or the window would compile against an incomplete
            # mirror and miss every emit (any activation they trigger
            # bumps activation_passes, which the steadiness guard below
            # turns into "not steady yet")
            self.engine._drain_checks()
        if self.engine.activation_passes != self._activation_passes:
            # recent drains still activated cold grains — not steady yet
            self._activation_passes = self.engine.activation_passes
            self._count = 1
            return False
        if all(len(pat.static_keys) == len(e[2])
               for pat, e in zip(self._patterns, entries)):
            return False  # nothing varies per tick: no window axis
        if self._program is None and not self._engage(sig, entries):
            return False
        # consume this tick into the window buffer.  Overlapped h2d
        # (config.overlap_h2d): per-tick numpy slabs start their device
        # copy NOW — the transfer rides under the currently-executing
        # window instead of serializing into the next window's dispatch
        # (stack_source then jnp.stacks device leaves, itself async).
        overlap = cfg.overlap_h2d

        def stage(v):
            if overlap and isinstance(v, np.ndarray) and v.ndim:
                return jax.device_put(v)
            return v

        for key, _b, _args, _p in entries:
            self.engine.queues[key].clear()
        self._buffer.append([
            {k: stage(v) for k, v in args.items()
             if k not in pat.static_keys}
            for pat, (_key, _b, args, _p) in zip(self._patterns, entries)])
        if len(self._buffer) >= cfg.auto_fusion_window:
            self._run_window()
        return True

    def _engage(self, sig: Tuple, entries: List[Tuple]) -> bool:
        from orleans_tpu.tensor.fused import FusedTickProgram

        prog = self._programs.get(sig)
        if prog is not None and (
                len(prog.sources) != len(entries)
                or any(not np.array_equal(s.keys, e[1].keys_host)
                       for s, e in zip(prog.sources, entries))):
            prog = None  # content-digest collision: never reuse blindly
        if prog is None:
            # clustered silos: every source's key set must be entirely
            # ring-owned here (same contract as engine.fuse_ticks)
            router = self.engine.router
            if router is not None:
                for _key, b, _args, _p in entries:
                    _local, remote = router.partition(_key[0], b.keys_host)
                    if remote:
                        self._disabled[sig] = self._ring_version()
                        self._reset()
                        return False
            prog = FusedTickProgram.multi(
                self.engine,
                [(key[0], key[1], b.keys_host)
                 for key, b, _args, _p in entries])
            # donation per config (the pipelined default): windows
            # double-buffer state in place; the rollback snapshot is
            # then a copy-before-donate device copy (_run_window).
            # Undonated (the A/B baseline) the pre-run buffers stay
            # valid and the snapshot is free references, as before.
            self._programs[sig] = prog
        # (re-)pin the donation mode at engagement: a cached program
        # compiled under the other mode re-traces in prepare() (cause
        # config_toggle) before its first window runs
        prog.donate = self.engine.config.donate_state
        for pat, (_key, _b, args, _p) in zip(self._patterns, entries):
            pat.static_args = {k: args[k] for k in pat.static_keys}
        if prog._compiled is None:
            # compile NOW, not when the first window fills: the compile
            # stall lands on the engagement tick instead of surprising a
            # steady-state window mid-run.  jax.jit is lazy, so lower +
            # AOT-compile against the exact window avals — no device
            # execution, and run() then calls the compiled executable
            # directly (window shape and arg structure are fixed for the
            # engagement's lifetime).
            t_compile = time.perf_counter()
            wrapped = prog._build(
                [dict(e[2]) for e in entries] if prog._is_multi()
                else dict(entries[0][2]))
            W = self.engine.config.auto_fusion_window

            def aval(v):
                a = np.asarray(v)
                return jax.ShapeDtypeStruct((W,) + a.shape, a.dtype)

            stacked0 = [
                {k: aval(v) for k, v in e[2].items()
                 if k not in pat.static_keys}
                for pat, e in zip(self._patterns, entries)]
            statics0 = [pat.static_args for pat in self._patterns]
            states = {n: self.engine.arena_for(n).state
                      for n in prog._touched}
            prog._compiled = wrapped.lower(
                states, statics0, stacked0,
                jnp.zeros(2, jnp.int32),
                self.engine.ledger.device_hist_in(),
                prog.attr_state_in(), prog.xneed_state_in()).compile()
            prog._reshard_count = self.engine.reshard_count
            # churn attribution: the engagement's AOT lower+compile is
            # the one fused site where the FULL lowering wall time is
            # visible (jit-path builds defer compile to first call)
            from orleans_tpu.tensor.profiler import CAUSE_NEW_WINDOW
            self.engine.compile_tracker.record(
                CAUSE_NEW_WINDOW,
                key="autofuse:" + "+".join(
                    f"{k[0]}.{k[1]}" for k, _b, _a, _p in entries),
                seconds=time.perf_counter() - t_compile,
                tick=self.engine.tick_number)
        self._program = prog
        return True

    # ================= window execution ====================================

    def _run_window(self) -> None:
        engine = self.engine
        prog = self._program
        t0 = time.perf_counter()

        # a generation change since the trace forces a settle of the
        # outstanding chain BEFORE this window pops from the buffer: if
        # the settle rolls back, its replay drains the chained ticks AND
        # this window (still buffered) through the unfused path while
        # the pattern state is intact — no orphan window can exist
        if prog._compiled is None or any(
                engine.arena_for(n).generation != g
                for n, g in prog._generations.items()) or any(
                engine.arena_for(n).eviction_epoch != e
                for n, e in prog._epochs.items()):
            # epoch mismatch counts too: free-list eviction leaves rows
            # in place but stales the program's baked directory mirror —
            # prepare() below re-traces against the post-eviction layout
            self._settle_chain()
            if self._program is None or not self._patterns:
                # the settle rolled back and reset detection: the
                # buffered ticks (this window included) were already
                # replayed unfused — nothing left to run fused
                return
        window = self._buffer
        self._buffer = []

        def stack_source(i: int) -> Dict[str, Any]:
            first = window[0][i]
            return {
                k: (jnp.stack([w[i][k] for w in window])
                    if isinstance(first[k], jax.Array)
                    else np.stack([np.asarray(w[i][k]) for w in window]))
                for k in first}

        stackeds = [stack_source(i) for i in range(len(self._patterns))]
        statics = [pat.static_args for pat in self._patterns]
        # resolve/rebuild BEFORE the chain snapshot: re-resolution can
        # auto-activate evicted source keys and GROW an arena — a grow
        # after the snapshot would make it unrestorable (the chain is
        # empty here whenever prepare has real work to do: the
        # generation-mismatch settle above ran first)
        prog.prepare(stackeds if prog._is_multi() else stackeds[0],
                     statics if prog._is_multi() else statics[0])
        if self._chain_snapshot is None:
            # chain start: the rollback pin.  Undonated programs leave
            # the pre-run buffers valid, so plain references suffice.
            # DONATED programs consume them — copy-before-donate: one
            # compiled device-side copy per touched arena, taken
            # before the first donated window of the chain runs, so a
            # rollback never reads a donated-away buffer.
            if prog.donate:
                t_pin = time.perf_counter()
                sizer = getattr(_pin_copy, "_cache_size", None)
                pins0 = sizer() if callable(sizer) else None
                snapshot = {n: dict(_pin_copy(engine.arena_for(n).state))
                            for n in prog._touched}
                if pins0 is not None and sizer() > pins0:
                    # the pin's jit traced+compiled synchronously inside
                    # the call (first donated chain over this column
                    # structure, or a capacity grow) — attributed like
                    # every other compile site; the cache-size delta
                    # keeps cache hits from recording phantom events
                    from orleans_tpu.tensor.profiler import \
                        CAUSE_NEW_WINDOW
                    engine.compile_tracker.record(
                        CAUSE_NEW_WINDOW,
                        key="pin_copy:" + "+".join(sorted(prog._touched)),
                        seconds=time.perf_counter() - t_pin,
                        tick=engine.tick_number)
            else:
                snapshot = {n: dict(engine.arena_for(n).state)
                            for n in prog._touched}
            self._chain_prog = prog
            self._chain_snapshot = snapshot
            self._chain_counters = (engine.tick_number, engine.ticks_run,
                                    engine.messages_processed)
            self._chain_generations = {
                n: engine.arena_for(n).generation for n in prog._touched}
            self._chain_epochs = {
                n: engine.arena_for(n).eviction_epoch
                for n in prog._touched}
            # the latency ledger and the attribution plane accumulate
            # INSIDE the windows: a rollback must also undo those
            # counts (the unfused replay re-records every message)
            self._chain_ledger = engine.ledger.snapshot_state()
            self._chain_attr = engine.attribution.snapshot_state()

        prog.run(stackeds if prog._is_multi() else stackeds[0],
                 static_args=statics if prog._is_multi() else statics[0])
        self._unverified.append(window)
        # the window advanced the tick clock: honor the periodic
        # checkpoint cadence in the fused steady state too — but VERIFY
        # FIRST.  A checkpoint taken before verification could persist
        # non-exact state (a hard kill before the rollback replay would
        # then restore missed deliveries as fact), so a due checkpoint
        # settles the chain and only then writes.  On a clean settle the
        # write below is a verified-exact restore point; on rollback the
        # replay runs unfused ticks that checkpoint at their own
        # boundaries, and the write below covers any remainder.
        if engine.checkpoint_due():
            self._settle_chain()
            engine.maybe_periodic_checkpoint()
        dt = time.perf_counter() - t0
        self.windows_run += 1
        for _ in range(len(window)):
            # every message in the window completes by window end — record
            # the window wall time as each tick's (conservative) latency
            engine.tick_durations.append(dt)
        if len(self._unverified) >= max(
                1, engine.config.auto_fusion_verify_windows):
            self._settle_chain()

    def _settle_chain(self) -> None:
        """Read the chain's accumulated device-side miss counter (ONE
        completion observation for up to verify_windows windows).  Zero:
        the chain was exact.  Nonzero: roll the state back to the chain
        start and replay every chained tick (plus any newer buffered
        ticks, in order) through the unfused path."""
        if not self._unverified:
            return
        engine = self.engine
        prog = self._chain_prog
        windows, self._unverified = self._unverified, []
        snapshot = self._chain_snapshot
        counters = self._chain_counters
        generations = self._chain_generations
        epochs = self._chain_epochs
        ledger_state = self._chain_ledger
        attr_state = self._chain_attr
        self._chain_prog = None
        self._chain_snapshot = None
        self._chain_counters = None
        self._chain_generations = {}
        self._chain_epochs = {}
        self._chain_ledger = None
        self._chain_attr = None
        misses = prog.verify()
        n_ticks = sum(len(w) for w in windows)
        if misses == 0:
            self.ticks_fused += n_ticks
            # a clean chain forgives earlier strikes: the ban targets
            # patterns whose windows roll back back-to-back, not a
            # steady pattern with a rare cold-key incident
            self._rollback_counts.pop(self._sig, None)
            return
        # non-exact chain (cold destination, fan-out overflow, round-cap
        # spill): roll back and replay unfused — the slow path that
        # keeps transparency exact.  A mid-chain repack is structurally
        # impossible: every row move (growth/compaction/reshard) settles
        # the owning engine's chain FIRST while the snapshot is still
        # restorable (GrainArena._settle_owner_chain), and queued traffic
        # breaks the pattern — which settles — before it can touch an
        # arena.  A generation mismatch here is therefore a bug, not an
        # operating condition.
        if any(engine.arena_for(n).generation != g
               for n, g in generations.items()) or any(
               engine.arena_for(n).eviction_epoch != e
               for n, e in epochs.items()):
            # a hard invariant, not an operating condition — raise (not
            # assert: -O must not turn this into restoring an
            # old-generation snapshot over a repacked arena).  Eviction
            # epochs are covered too: every deactivation path settles
            # the owner chain BEFORE freeing rows, so a mid-chain
            # eviction equally means the snapshot discipline was
            # bypassed (the snapshot holds pre-eviction columns).
            raise RuntimeError(
                "autofuse: arena repacked or evicted mid-chain — a row "
                "move/free bypassed _settle_owner_chain; rollback "
                "snapshot is unrestorable")
        self.windows_rolled_back += 1
        for n, cols in snapshot.items():
            # restore the pin (a copy under donation — the donated
            # buffers themselves are long gone, which is exactly why
            # the pin was copied before the first donated run)
            engine.arena_for(n).adopt_state(cols)
        (engine.tick_number, engine.ticks_run,
         engine.messages_processed) = counters
        if ledger_state is not None:
            # drop the rolled-back windows' in-program accumulation —
            # the unfused replay below re-records every message
            engine.ledger.restore_state(ledger_state)
        if attr_state is not None:
            # attribution counts rolled back the same way (bit-exact
            # sketch/count survival is the plane's acceptance contract)
            engine.attribution.restore_state(attr_state)
        sig = self._sig
        strikes = self._rollback_counts.get(sig, 0) + 1
        self._rollback_counts[sig] = strikes
        if strikes >= max(1, engine.config.auto_fusion_max_rollbacks):
            # hysteresis: a pattern that repeatedly rolls back is paying
            # for fusion without getting it — ban the signature until the
            # ring (or arena generation, which is part of the sig) changes
            self._disabled[sig] = self._ring_version()
            self._programs.pop(sig, None)
        # chained ticks replay FIRST, then any newer buffered ticks
        self._buffer = [t for w in windows for t in w] + self._buffer
        self._replay_buffer()  # in order, unfused, BEFORE any newer work
        self._reset()

    # ================= drain integration ==================================

    def flush_partial(self) -> bool:
        """Re-enqueue ONE buffered tick for exact unfused replay (the
        engine's drain loop calls this until it returns False).  One tick
        per call preserves per-tick application order; every pattern's
        batch of that tick re-enqueues together, matching how the tick
        originally arrived.  Settles the verification chain first —
        flush means FULL delivery, including any rollback-replay the
        chain still owes."""
        if self._unverified and not self._replaying:
            self._settle_chain()
            return True
        if not self._buffer:
            self._replaying = False
            return False
        from orleans_tpu.tensor.engine import PendingBatch

        self._replaying = True
        tick_args = self._buffer.pop(0)
        for pat, per_tick in zip(self._patterns, tick_args):
            self.engine.queues[pat.key].append(PendingBatch(
                args={**pat.static_args, **per_tick},
                rows=pat.rows,
                keys_host=pat.keys_host,
                generation=pat.generation,
                epoch=pat.epoch,
                # replayed buffered ticks re-enter the unfused ledger
                # path; stamp them at replay time so they are counted
                # (once — the fused window they fell out of never ran)
                inject_tick=self.engine.tick_number))
        return True

    def snapshot(self) -> Dict[str, int]:
        return {
            "windows_run": self.windows_run,
            "windows_rolled_back": self.windows_rolled_back,
            "ticks_fused": self.ticks_fused,
        }
