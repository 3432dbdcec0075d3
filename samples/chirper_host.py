"""Chirper on the host (per-message) path — follower fan-out as classic
virtual actors.

Same workload as samples/chirper.py but one RPC per follower delivery,
structurally the reference's execution model (reference:
Samples/Chirper/ChirperGrains/ChirperAccount.cs:129-156 PublishMessage —
one NewChirp call per follower awaited with WhenAll; AddFollower :235;
NewChirp :261 with the bounded received-message cache).  Used by tests
as the host-path parity surface for the chirper workload.
"""

from __future__ import annotations

import asyncio
from collections import deque
from typing import Deque, List

from orleans_tpu import Grain, grain_interface
from orleans_tpu.core.grain import grain_class, reentrant

RECEIVED_CACHE_SIZE = 100  # reference: ChirperAccount ReceivedMessagesCacheSize


@grain_interface
class IHostChirperAccount:
    async def follow(self, publisher: int): ...
    async def add_follower(self, follower: int): ...
    async def publish(self, chirp_id: int): ...
    # NOT one-way: publish awaits every delivery, matching the reference's
    # Task.WhenAll over subscriber NewChirp calls (ChirperAccount.cs:156) —
    # and keeping the bench baseline honest (one-way would stop the clock
    # before any delivery executed)
    async def new_chirp(self, chirp_id: int, author: int): ...
    async def received_count(self) -> int: ...
    async def recent_chirps(self) -> list: ...


@grain_class
@reentrant
class HostChirperAccountGrain(Grain, IHostChirperAccount):
    """Reentrant: publish awaits every follower's new_chirp, and follow
    graphs have cycles — without interleaving, two accounts publishing to
    each other would deadlock their turns (the classic awaited-fan-out
    cycle; the reference mitigates the same hazard with [Reentrant])."""
    def __init__(self) -> None:
        self.followers: List[int] = []
        self.following: List[int] = []
        self.published = 0
        self.received = 0
        self.recent: Deque = deque(maxlen=RECEIVED_CACHE_SIZE)

    async def follow(self, publisher: int):
        """(reference: FollowUserId :181 → publisher.AddFollower)"""
        if publisher not in self.following:
            self.following.append(publisher)
            pub = self.get_grain(IHostChirperAccount, publisher)
            await pub.add_follower(self.grain_id.primary_key_int)

    async def add_follower(self, follower: int):
        if follower not in self.followers:
            self.followers.append(follower)

    async def publish(self, chirp_id: int):
        """One NewChirp RPC per follower, awaited together (reference:
        PublishMessage :129 — Task.WhenAll over subscriber calls)."""
        self.published += 1
        me = self.grain_id.primary_key_int
        await asyncio.gather(*(
            self.get_grain(IHostChirperAccount, f).new_chirp(chirp_id, me)
            for f in self.followers))

    async def new_chirp(self, chirp_id: int, author: int):
        self.received += 1
        self.recent.append((chirp_id, author))

    async def received_count(self) -> int:
        return self.received

    async def recent_chirps(self) -> list:
        return list(self.recent)
