"""Chat rooms — a million-user scenario riding the device streams plane
(tensor/streams_plane.py).

A small-ish set of STREAMS (chat rooms) with a large, churning
SUBSCRIBER population (users).  The reference would run this as pub-sub
over grains — one rendezvous lookup + one grain call per (event,
consumer) (PubSubRendezvousGrain + PersistentStreamPullingAgent); here the
subscriber adjacency lives on device as arena CSR and a whole tick's
publishes fan out in one gather + segment reduction.

Exactness oracle (the routing-sweep discipline): every loader can REPLAY
its publish history against the HOST adjacency (numpy ``np.add.at`` /
``np.maximum.at`` — the per-event pub-sub delivery semantics, one
virtual grain call per (event, subscriber)) and compare the device
arenas field for field.  All checked fields are integers, so equality is
EXACT — the device delivery multiset equals the host replay or the test
fails, at every churn point (subscribe / unsubscribe / evict / slot
reuse).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from orleans_tpu.core.grain import batched_method
from orleans_tpu.tensor import (
    Batch,
    DeviceSubscriptions,
    VectorGrain,
    field,
    seg_max,
    seg_sum,
    vector_grain,
)

#: checksum mixers (primes) — integer, so device vs host equality is exact
_MSG_MIX = 1009
_SRC_MIX = 97


@vector_grain
class ChatRoomGrain(VectorGrain):
    """Stream ingress: one row per room.  ``publish`` records the
    room-side effects; delivery to every member rides the registered
    DeviceSubscriptions (engine.register_subscriptions)."""

    published = field(jnp.int32, 0)
    last_msg = field(jnp.int32, -1)

    @batched_method
    @staticmethod
    def publish(state, batch: Batch, n_rows: int):
        rows = batch.rows
        ones = jnp.asarray(batch.mask, jnp.int32)
        msg = jnp.where(batch.mask,
                        jnp.asarray(batch.args["msg_id"], jnp.int32), -1)
        return {
            **state,
            "published": state["published"] + seg_sum(ones, rows, n_rows),
            "last_msg": jnp.maximum(state["last_msg"],
                                    seg_max(msg, rows, n_rows)),
        }


@vector_grain
class ChatUserGrain(VectorGrain):
    """Subscriber: one row per user.  ``receive`` is segment-aware — a
    pull-mode delivery (lanes grouped by user row, Batch.segments) runs
    entirely scatter-free; push-mode redeliveries use the same handler
    through the ordinary scatter reductions."""

    received = field(jnp.int32, 0)
    last_msg = field(jnp.int32, -1)
    checksum = field(jnp.int32, 0)

    @batched_method
    @staticmethod
    def receive(state, batch: Batch, n_rows: int):
        rows, args, seg = batch.rows, batch.args, batch.segments
        ones = jnp.where(batch.mask, 1, 0).astype(jnp.int32)
        msg = jnp.asarray(args["msg_id"], jnp.int32)
        src = jnp.asarray(args["src_key"], jnp.int32)
        mix = jnp.where(batch.mask,
                        msg % _MSG_MIX + src % _SRC_MIX, 0)
        return {
            **state,
            "received": state["received"]
            + seg_sum(ones, rows, n_rows, segments=seg),
            "last_msg": jnp.maximum(
                state["last_msg"],
                seg_max(jnp.where(batch.mask, msg, -1), rows, n_rows,
                        segments=seg, fill=-1)),
            "checksum": state["checksum"]
            + seg_sum(mix, rows, n_rows, segments=seg),
        }


# ---------------------------------------------------------------------------
# graph construction
# ---------------------------------------------------------------------------

def build_membership(n_streams: int, n_subscribers: int,
                     mean_memberships: float = 3.0, zipf_a: float = 1.2,
                     seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """(stream_keys, sub_keys) edge arrays: room popularity ~ Zipf
    (a few huge rooms, a long tail — the power-law stress), every
    subscriber belongs to at least one stream."""
    rng = np.random.default_rng(seed)
    n_edges = int(n_subscribers * mean_memberships)
    ranks = rng.permutation(n_streams) + 1
    weights = ranks.astype(np.float64) ** (-zipf_a)
    weights /= weights.sum()
    streams = rng.choice(n_streams, size=n_edges, p=weights)
    subs = np.concatenate([
        np.arange(n_subscribers),                       # coverage
        rng.integers(0, n_subscribers, n_edges - n_subscribers),
    ]) if n_edges >= n_subscribers else rng.integers(
        0, n_subscribers, n_edges)
    return streams.astype(np.int64), subs.astype(np.int64)


class _HostMirror:
    """The oracle's expected subscriber state, advanced per publish by
    the HOST pub-sub semantics (one virtual delivery per (event,
    subscriber)); re-derives its expansion whenever the adjacency
    changes."""

    def __init__(self, subs: DeviceSubscriptions, n_users: int) -> None:
        self.subs = subs
        self.received = np.zeros(n_users, np.int64)
        self.last_msg = np.full(n_users, -1, np.int64)
        self.checksum = np.zeros(n_users, np.int64)
        self._streams: Optional[np.ndarray] = None
        self._dsts: Optional[np.ndarray] = None
        self._srcs: Optional[np.ndarray] = None
        self._version = -1

    def _expansion(self, stream_keys: np.ndarray):
        if self._version != self.subs.layout_version \
                or self._streams is None \
                or not np.array_equal(self._streams, stream_keys):
            dsts, srcs = self.subs.host_expand(stream_keys)
            self._streams = stream_keys.copy()
            self._dsts, self._srcs = dsts, srcs
            self._version = self.subs.layout_version
        return self._dsts, self._srcs

    def publish(self, stream_keys: np.ndarray, msg: np.ndarray) -> None:
        dsts, srcs = self._expansion(stream_keys)
        v = msg[srcs].astype(np.int64)
        sk = stream_keys[srcs].astype(np.int64)
        np.add.at(self.received, dsts, 1)
        np.maximum.at(self.last_msg, dsts, v)
        np.add.at(self.checksum, dsts, v % _MSG_MIX + sk % _SRC_MIX)

    def evict_keys(self, keys: np.ndarray) -> None:
        """Mirror invalidation on adjacency-affecting eviction (the
        subscription survives eviction — delivery reactivates — so the
        expected state does NOT change; only the cached expansion may)."""
        self._version = -1


def check_chat_exact(engine, n_users: int,
                     mirror: _HostMirror) -> Dict[str, bool]:
    """Device arenas vs the host replay — exact integer equality (the
    delivery-multiset oracle: counts + order-free checksums + max)."""
    arena = engine.arena_for("ChatUserGrain")
    users = np.arange(n_users, dtype=np.int64)
    rows, ok = arena.lookup_rows(users)
    live = ok
    got_recv = np.asarray(arena.state["received"])[rows]
    got_max = np.asarray(arena.state["last_msg"])[rows]
    got_sum = np.asarray(arena.state["checksum"])[rows]
    return {
        "received_exact": bool(
            np.array_equal(got_recv[live], mirror.received[live])),
        "max_exact": bool(np.array_equal(got_max[live],
                                         mirror.last_msg[live])),
        "checksum_exact": bool(
            np.array_equal(got_sum[live], mirror.checksum[live])),
        "live_subscribers": int(live.sum()),
    }


# ---------------------------------------------------------------------------
# load drivers
# ---------------------------------------------------------------------------

def wire_chat(engine, n_rooms: int, n_users: int,
              mean_memberships: float = 3.0, seed: int = 0,
              subs: Optional[DeviceSubscriptions] = None
              ) -> DeviceSubscriptions:
    """Build the room→member adjacency, register it as the engine's
    publish route, and pre-activate + bind the steady publish pattern."""
    if subs is None:
        subs = DeviceSubscriptions(engine, "ChatUserGrain", "receive")
        streams, members = build_membership(n_rooms, n_users,
                                            mean_memberships, seed=seed)
        subs.subscribe_many(streams, members)
    engine.register_subscriptions("ChatRoomGrain", "publish", subs)
    engine.arena_for("ChatUserGrain").reserve(n_users)
    engine.arena_for("ChatUserGrain").resolve_rows(
        np.arange(n_users, dtype=np.int64))
    engine.arena_for("ChatRoomGrain").reserve(n_rooms)
    subs.bind(np.arange(n_rooms, dtype=np.int64))
    return subs


async def run_chat_load(engine, n_rooms: int = 1_000,
                        n_users: int = 100_000,
                        mean_memberships: float = 3.0,
                        n_ticks: int = 16, seed: int = 0,
                        subs: Optional[DeviceSubscriptions] = None,
                        verify: bool = False,
                        mirror: Optional[_HostMirror] = None
                        ) -> Dict[str, float]:
    """Every room gets one published message per tick; members absorb
    the fan-in through the plane.  Message accounting matches the
    reference's pub-sub: one publish per room + one delivery per
    (event, member edge)."""
    import jax as _jax

    subs = wire_chat(engine, n_rooms, n_users, mean_memberships, seed,
                     subs=subs)
    rooms = np.arange(n_rooms, dtype=np.int64)
    injector = engine.make_injector("ChatRoomGrain", "publish", rooms)
    if verify and mirror is None:
        mirror = _HostMirror(subs, n_users)
    arena = engine.arena_for("ChatUserGrain")
    edges = subs.edge_count

    msg_base = np.int32(seed * 1_000_000)
    t0 = time.perf_counter()
    for t in range(n_ticks):
        msg_ids = (np.arange(n_rooms, dtype=np.int32)
                   + np.int32(t * n_rooms) + msg_base)
        injector.stage({"msg_id": msg_ids})
        injector.inject()
        await engine.drain_queues()
        if mirror is not None:
            mirror.publish(rooms, msg_ids.astype(np.int64))
    await engine.flush()
    _jax.block_until_ready(arena.state["received"])
    elapsed = time.perf_counter() - t0

    events = (n_rooms + edges) * n_ticks
    stats: Dict[str, float] = {
        "rooms": n_rooms, "users": n_users, "edges": edges,
        "ticks": n_ticks, "seconds": elapsed, "events": events,
        "events_per_sec": events / elapsed,
    }
    if mirror is not None:
        stats["oracle"] = check_chat_exact(engine, n_users, mirror)
        stats["mirror"] = mirror
    return stats


async def run_chat_stream_load(silo, provider_name: str = "cstream",
                               n_rooms: int = 1_000,
                               n_users: int = 100_000,
                               mean_memberships: float = 3.0,
                               n_slabs: int = 10, seed: int = 0,
                               subs: Optional[DeviceSubscriptions] = None
                               ) -> Dict[str, float]:
    """The PERSISTENT-STREAMS pipeline end to end, on the device plane:
    producers enqueue slab items into the durable queue, the pulling
    agent drains them in batched dequeue/ack transactions, the tensor
    sink injects each pull cycle's slab (staged h2d under the previous
    slab's compute), and the engine's registered subscriptions fan the
    publishes out to every member — the queue-fed twin of
    run_chat_load.  The silo must host a provider named
    ``provider_name`` with ``bind_tensor_sink("chat-pub",
    "ChatRoomGrain", "publish")``; call ``wire_chat`` on its engine
    first (or pass ``subs``)."""
    import asyncio

    from orleans_tpu.streams.core import StreamId

    provider = silo.stream_providers[provider_name]
    engine = silo.tensor_engine
    subs = wire_chat(engine, n_rooms, n_users, mean_memberships, seed,
                     subs=subs)
    edges = subs.edge_count
    rooms = np.arange(n_rooms, dtype=np.int64)
    stream_id = StreamId(provider=provider_name, namespace="chat-pub",
                         key=0)
    slabs = [{"key": rooms.copy(),
              "msg_id": (np.arange(n_rooms, dtype=np.int32)
                         + np.int32(t * n_rooms))}
             for t in range(n_slabs)]
    agents = provider.manager.agents
    delivered0 = sum(a.delivered for a in agents.values())

    t0 = time.perf_counter()
    for slab in slabs:
        await provider.produce(stream_id, [slab])
    while sum(a.delivered for a in agents.values()) - delivered0 \
            < n_slabs:
        await asyncio.sleep(0.002)
    await engine.flush()
    import jax as _jax
    _jax.block_until_ready(
        engine.arena_for("ChatUserGrain").state["received"])
    elapsed = time.perf_counter() - t0

    # one queue event per (slab, room) + one delivery per member edge
    messages = (n_rooms + edges) * n_slabs
    return {
        "rooms": n_rooms, "users": n_users, "edges": edges,
        "slabs": n_slabs, "seconds": elapsed, "messages": messages,
        "messages_per_sec": messages / elapsed,
        "pipeline": "producer → durable queue (batched enqueue) → "
                    "pulling agent (ONE dequeue+ack transaction per "
                    "cycle) → staged slab → ChatRoomGrain.publish → "
                    "device subscription fan-out (pull-mode)",
    }
