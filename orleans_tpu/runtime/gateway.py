"""Client gateway: the silo-side edge for out-of-cluster clients.

Parity: reference Gateway inside gateway-silos (reference:
src/OrleansRuntime/Messaging/Gateway.cs:37 — per-client ClientState,
RecordOpenedSocket :109, reply routing via TryDeliverToProxy,
MessageCenter.cs:55) and the ClientObserverRegistrar system target that
registers client ids in the grain directory so any silo can route
observer calls (reference: ClientObserverRegistrar.cs:35).

Two client edges share one Gateway object:

* in-process — the client hands a deliver callable straight to
  ``connect_client`` (the test/embedded mode);
* TCP — ``GatewayAcceptor`` listens on a dedicated client port (the
  reference's ProxyGatewayEndpoint, distinct from the silo-to-silo
  port; accept side GatewayAcceptor.cs:32): a connection opens with a
  codec-framed HELLO control record carrying the client id, after which
  Message frames flow both ways on the same socket.
"""

from __future__ import annotations

import asyncio
import dataclasses
import struct
import time
from collections import defaultdict
from typing import Any, Callable, Dict, Optional, Tuple

from orleans_tpu import codec as codec_mod
from orleans_tpu import spans as _spans
from orleans_tpu.codec import RpcFrame, default_manager as codec
from orleans_tpu.ids import ActivationAddress, ActivationId, GrainId
from orleans_tpu.runtime.messaging import Direction, Message

#: gateway wire framing: 4-byte magic + 4-byte length, codec payload.
#: Payloads are either a Message or a control dict {"op": ...}.
GATEWAY_MAGIC = 0x4F43  # "OC" — distinct from silo-to-silo frames
#: rpc fast-path frames (codec.encode_rpc_calls/results): same 8-byte
#: header, but the payload is the fixed-header batched-call format —
#: NEVER walked by the token-stream codec
GATEWAY_RPC_MAGIC = 0x4F52  # "OR"


class Gateway:
    """System target 'gateway' on every silo."""

    def __init__(self, silo) -> None:
        self.silo = silo
        # client grain id → deliver callable (the 'socket' to the client)
        self._clients: Dict[GrainId, Callable[[Message], None]] = {}
        # ids whose connection is a REAL socket (fidelity roundtrip skipped)
        self._wired: set = set()
        self.wire_fidelity = True

    @property
    def alive(self) -> bool:
        from orleans_tpu.runtime.silo import SiloStatus
        return self.silo.status == SiloStatus.ACTIVE

    # -- connection management (reference: Gateway.RecordOpenedSocket :109)

    async def connect_client(self, client_id: GrainId,
                             deliver: Callable[[Message], None],
                             wired: bool = False) -> None:
        """``wired=True`` marks a connection whose messages cross a REAL
        socket (GatewayAcceptor) — the wire-fidelity codec roundtrip that
        emulates a socket for in-proc clients is skipped for those."""
        self._clients[client_id] = deliver
        if wired:
            self._wired.add(client_id)
        await self._register_client_route(client_id)

    async def disconnect_client(self, client_id: GrainId) -> None:
        self._clients.pop(client_id, None)
        self._wired.discard(client_id)
        addr = ActivationAddress(self.silo.address, client_id,
                                 ActivationId(0, 0))
        try:
            await self.silo.grain_directory.unregister(addr)
        except Exception:
            pass

    async def register_observer(self, client_id: GrainId,
                                observer_id: GrainId) -> None:
        """Route an observer id to this client's connection
        (reference: ClientObserverRegistrar registration)."""
        deliver = self._clients.get(client_id)
        if deliver is None:
            raise KeyError(f"client {client_id} not connected to this gateway")
        self._clients[observer_id] = deliver
        if client_id in self._wired:
            self._wired.add(observer_id)
        await self._register_client_route(observer_id)

    async def _register_client_route(self, grain_id: GrainId) -> None:
        """Register the client id in the grain directory so messages from
        any silo route to this gateway silo."""
        addr = ActivationAddress(self.silo.address, grain_id,
                                 ActivationId(0, 0))
        await self.silo.grain_directory.register_single_activation(addr)

    async def reregister_routes(self) -> None:
        """Re-assert client routes after ring ownership changed."""
        for grain_id in list(self._clients):
            try:
                await self._register_client_route(grain_id)
            except Exception:
                pass

    # -- inbound from clients ----------------------------------------------

    def submit(self, msg: Message, already_wired: bool = False) -> None:
        """A client pushed a message into the cluster through this silo
        (reference: GatewayAcceptor receive → MessageCenter inbound).
        ``already_wired`` skips the fidelity roundtrip for messages that
        arrived over a real socket (they were just deserialized)."""
        if self.wire_fidelity and not already_wired:
            msg = codec.deserialize(codec.serialize(msg))
        rec = self.silo.spans
        if rec.enabled and msg.direction != Direction.RESPONSE:
            trace = _spans.trace_of(msg)
            if trace is None:
                # a client that doesn't trace (old/raw edge): THIS is the
                # trace ingress — mint the context here so every hop
                # behind the gateway is still attributable
                trace = rec.begin_trace()
                if trace is not None:
                    span = rec.start(f"gateway {msg.method_name}",
                                     "gateway.ingress", trace,
                                     client=str(msg.sending_grain))
                    msg.request_context = rec.inject(msg.request_context,
                                                     trace, span)
                    rec.finish(span)
            else:
                rec.event(f"gateway {msg.method_name}", "gateway.forward",
                          trace, client=str(msg.sending_grain))
        if msg.target_silo is None:
            # gateway addresses the message like any in-silo send
            self.silo.dispatcher.send_message(msg)
        else:
            self.silo.message_center.send_message(msg)

    # -- inbound vector batches (the batched client edge) -------------------

    def submit_batch(self, type_name: str, method: str, keys, args,
                     want_results: bool = False):
        """A client pushed a whole (keys, args) vector slab through this
        silo — the batched client edge the north star demands ('batched
        adjacency+payload tensors' instead of the reference's per-message
        Gateway.cs:37 proxy loop).  Routes through the tensor engine —
        in cluster mode that is the VectorRouter's ownership split —
        NEVER through the per-message dispatcher."""
        engine = self.silo.tensor_engine
        if engine is None:
            raise RuntimeError(
                f"silo {self.silo.name} has no tensor engine; vector "
                f"batches need one (config.tensor.enabled)")
        return engine.send_batch(type_name, method, keys, args,
                                 want_results=want_results)

    def submit_calls(self, calls: list) -> None:
        """Batched RPC ingress (the per-call analog of ``submit_batch``):
        a whole window of host-grain calls from a wired client enters
        the silo as ONE batch — the coalescer groups them into
        (type, method) invoke windows.  When the batched plane is not
        accepting (live-disabled, ring at bound) every call degrades to
        the per-message pipeline — same replies, counted as
        fallbacks — so a gateway never refuses traffic the silo could
        serve."""
        coal = self.silo.rpc
        if coal.accepting():
            for call in calls:
                coal.submit(call)
        else:
            loop = asyncio.get_running_loop()
            dispatcher = self.silo.dispatcher
            for call in calls:
                dispatcher._window_fallback(call, loop)

    def send_client_batch(self, type_name: str, method: str, keys, args,
                          want_results: bool = False):
        """In-process client edge for vector slabs — wire-fidelity
        roundtrips the slab through the codec (the ndarray tokens a real
        socket would carry) before it enters the engine."""
        if self.wire_fidelity:
            keys, args = codec.deserialize(codec.serialize((keys, args)))
        return self.submit_batch(type_name, method, keys, args,
                                 want_results=want_results)

    # -- outbound to clients (reference: Gateway reply routing) ------------

    def deliver(self, msg: Message) -> None:
        deliver = self._clients.get(msg.target_grain)
        if deliver is None:
            self.silo.logger.warn(
                f"gateway: no client connection for {msg.target_grain}; "
                f"dropping {msg}")
            return
        if self.wire_fidelity and msg.target_grain not in self._wired:
            msg = codec.deserialize(codec.serialize(msg))
        asyncio.get_running_loop().call_soon(deliver, msg)


# ---------------------------------------------------------------------------
# TCP client edge (reference: GatewayAcceptor.cs:32 + proxied handshake,
# IncomingMessageAcceptor.cs:133)
# ---------------------------------------------------------------------------

def write_gateway_frame(writer: asyncio.StreamWriter, payload: Any) -> None:
    blob = codec.serialize(payload)
    writer.write(struct.pack("<II", GATEWAY_MAGIC, len(blob)) + blob)


def write_gateway_rpc_frame(writer: asyncio.StreamWriter,
                            segments: list) -> None:
    """Scatter-write one rpc fast-path frame: header + raw segments go
    out back to back — array payload bytes are memoryviews over the
    source buffers, never joined into a fresh bytes object here."""
    total = sum(len(memoryview(s).cast("B")) for s in segments)
    writer.write(struct.pack("<II", GATEWAY_RPC_MAGIC, total))
    for s in segments:
        writer.write(s)


async def read_gateway_frame(reader: asyncio.StreamReader) -> Any:
    """Read one control/Message frame (handshake surfaces); rpc
    fast-path frames are rejected here — pumps that speak both use
    :func:`read_gateway_frame_any`."""
    frame = await read_gateway_frame_any(reader)
    if isinstance(frame, RpcFrame):
        raise ValueError("unexpected rpc fast-path frame on a "
                         "control-only read")
    return frame


async def read_gateway_frame_any(reader: asyncio.StreamReader) -> Any:
    """Read one gateway frame of either flavor: token-stream payloads
    decode through the general codec, rpc fast-path payloads through
    the fixed-header decoder (returns :class:`codec.RpcFrame`)."""
    return decode_gateway_frame(*await read_gateway_payload(reader))


async def read_gateway_payload(reader: asyncio.StreamReader
                               ) -> Tuple[int, bytes]:
    """One frame's (magic, payload bytes), not yet decoded."""
    header = await reader.readexactly(8)
    magic, length = struct.unpack("<II", header)
    return magic, await reader.readexactly(length)


def decode_gateway_frame(magic: int, payload: bytes) -> Any:
    if magic == GATEWAY_MAGIC:
        return codec.deserialize(payload)
    if magic == GATEWAY_RPC_MAGIC:
        return codec_mod.decode_rpc_frame(codec, payload)
    raise ValueError(f"bad gateway frame magic {magic:#x}")


def _rebase_expiration_inbound(msg: Message) -> Message:
    if isinstance(msg, Message) and msg.expiration is not None:
        # wire carries remaining TTL → rebase on this host's clock
        # (same discipline as TcpTransport silo frames).  Batched rpc
        # frames carry a remaining-TTL COLUMN and rebase per call in
        # _handle_rpc_calls — one frame-level rebase would hand every
        # call the first call's deadline.
        msg.expiration = time.monotonic() + msg.expiration
    return msg


class _RpcBinding:
    """One negotiated rpc dictionary entry on a gateway connection:
    rpc_id → (interface, method, key→GrainId memo).  The client
    assigns ids and announces each once ({"op": "rpc_bind"}); the
    ordered stream guarantees the binding lands before any calls frame
    that uses it."""

    __slots__ = ("iface", "minfo", "_gids")

    def __init__(self, iface, minfo) -> None:
        self.iface = iface
        self.minfo = minfo
        self._gids: Dict[int, GrainId] = {}

    def gid(self, key: int) -> GrainId:
        g = self._gids.get(key)
        if g is None:
            from orleans_tpu.core.grain import grain_id_for
            g = grain_id_for(self.iface.cls, key)
            self._gids[key] = g
        return g


def _resolve_rpc_binding(frame: dict) -> _RpcBinding:
    from orleans_tpu.core.grain import get_interface
    iface = get_interface(frame["iface"])
    minfo = iface.methods_by_name.get(frame["method"])
    if minfo is None:
        raise KeyError(f"{frame['iface']} has no grain method "
                       f"{frame['method']!r}")
    if minfo.batched:
        raise ValueError("batched (vector) methods ride the "
                         "vector_batch slab op, not the rpc fast path")
    return _RpcBinding(iface, minfo)


_RPC_SHARED_SAFE = (str, int, float, bool, bytes, type(None))
#: exact scalar types a results frame may collapse to one shared value
_RPC_COMMON_RESULT_TYPES = frozenset((str, int, float, bool, bytes,
                                      type(None)))


def _rpc_args_shared_safe(args) -> bool:
    """True when one decoded args tuple may be handed to EVERY call of
    a common-args frame: immutable scalars and the decoder's read-only
    ndarray views share safely; anything mutable (a GENERAL-decoded
    list/dict) must deep-copy per call to keep the per-message path's
    isolation barrier."""
    import numpy as np
    for a in args:
        if isinstance(a, _RPC_SHARED_SAFE):
            continue
        if isinstance(a, np.ndarray) and not a.flags.writeable:
            continue
        return False
    return True


async def _rpc_reply(acceptor: "GatewayAcceptor",
                     writer: asyncio.StreamWriter, batch_id: int,
                     futures: list) -> None:
    """Resolve one calls-frame's futures into ONE results frame: status
    column + values (collapsed to a single shared value when the whole
    window answered identically — the steady-state helloworld shape)."""
    results = await asyncio.gather(*futures, return_exceptions=True)
    if writer.is_closing():
        return
    with acceptor.stage("reply"):
        _write_rpc_results(writer, batch_id, results)


def _write_rpc_results(writer: asyncio.StreamWriter, batch_id: int,
                       results: list) -> None:
    import numpy as np

    from orleans_tpu.runtime.messaging import RejectionType
    from orleans_tpu.runtime.runtime_client import RejectionError

    n = len(results)
    statuses = np.zeros(n, dtype=np.uint8)
    clean = True
    for i, res in enumerate(results):
        if isinstance(res, BaseException):
            clean = False
            if isinstance(res, RejectionError) \
                    and res.rejection == RejectionType.EXPIRED:
                statuses[i] = codec_mod.RPC_STATUS_EXPIRED
            else:
                statuses[i] = codec_mod.RPC_STATUS_ERROR
    common = False
    if clean and n > 1:
        first = results[0]
        # exact TYPE identity before ==: bool/int/float must never
        # collapse into each other, and the type check short-circuits
        # before an ndarray result could reach == (whose elementwise
        # answer would raise here and strand the whole reply frame)
        ftype = type(first)
        if ftype in _RPC_COMMON_RESULT_TYPES:
            common = all(type(r) is ftype and r == first
                         for r in results)
    try:
        if common:
            segments = codec_mod.encode_rpc_results(
                codec, batch_id, statuses, None,
                common_value=results[0], common=True)
        else:
            segments = codec_mod.encode_rpc_results(
                codec, batch_id, statuses, list(results))
    except Exception as exc:  # noqa: BLE001 — an unencodable result
        # must cost an error REPLY, never a frame that was never sent
        # (the client's futures would idle out their deadlines)
        statuses[:] = codec_mod.RPC_STATUS_ERROR
        segments = codec_mod.encode_rpc_results(
            codec, batch_id, statuses, None,
            common_value=RuntimeError(
                f"rpc reply not wire-serializable: {exc!r}"),
            common=True)
    write_gateway_rpc_frame(writer, segments)


def _with_ttl(msg: Message) -> Message:
    if msg.expiration is None:
        return msg
    return dataclasses.replace(
        msg, expiration=max(0.0, msg.expiration - time.monotonic()))


class GatewayAcceptor:
    """Dedicated client-facing listener on a gateway silo
    (reference: ProxyGatewayEndpoint + GatewayAcceptor.cs:32).

    The loop thread's own work per frame runs in three stages
    (``orleans.gateway.decode``: the payload's codec decode, not the
    socket reads; ``.submit``: the hand-off into the engine, the
    coalescer or the dispatcher; ``.reply``: encoding and writing the
    reply frame), counted in ``stage_seconds`` beside ``frames``, the
    frames decoded: the front door's time per frame."""

    STAGES = {k: "orleans.gateway." + k for k in ("decode", "submit",
                                                  "reply")}

    def __init__(self, silo, host: str = "127.0.0.1", port: int = 0) -> None:
        # imported here: the profiler imports jax, and clients import
        # this module's frame functions without it
        from orleans_tpu.tensor.profiler import Stage

        self.silo = silo
        self.host = host
        self.port = port
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set = set()
        self._stage = Stage
        self.frames = 0
        self.stage_seconds: Dict[str, float] = defaultdict(float)

    def stage(self, key: str):
        return self._stage(self.STAGES[key], self.stage_seconds, key)

    def snapshot(self) -> Dict[str, Any]:
        return {"frames": self.frames,
                "stage_seconds": dict(self.stage_seconds)}

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._on_conn, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    def close(self) -> None:
        if self._server is not None:
            self._server.close()
            self._server = None
        for w in list(self._conns):
            w.close()
        self._conns.clear()

    def _handle_rpc_calls(self, gateway: "Gateway",
                          writer: asyncio.StreamWriter,
                          client_id: GrainId,
                          rpc_bindings: Dict[int, Optional["_RpcBinding"]],
                          frame) -> None:
        """One decoded calls frame → one batch into the coalescer → one
        results frame (task) resolving every per-call future from the
        batched completion.  TTLs rebase PER CALL on this host's clock
        — the frame-level rebase bug class the regression test in
        tests/test_rpc.py pins."""
        if frame.kind != codec_mod.RPC_KIND_CALLS:
            raise ValueError("client sent a results frame")
        with self.stage("submit"):
            self._submit_rpc_calls(gateway, writer, client_id,
                                   rpc_bindings, frame)

    def _submit_rpc_calls(self, gateway: "Gateway",
                          writer: asyncio.StreamWriter, client_id: GrainId,
                          rpc_bindings: Dict[int, Optional["_RpcBinding"]],
                          frame) -> None:
        from orleans_tpu.runtime.rpc import _Call

        loop = asyncio.get_running_loop()
        want = frame.batch_id != 0 and not frame.one_way
        binding = rpc_bindings.get(frame.rpc_id)
        if binding is None:
            if want:
                import numpy as np
                err = RuntimeError(
                    f"rpc_id {frame.rpc_id} is not usably bound on this "
                    "connection")
                segments = codec_mod.encode_rpc_results(
                    codec, frame.batch_id,
                    np.full(frame.n, codec_mod.RPC_STATUS_ERROR,
                            dtype=np.uint8),
                    None, common_value=err, common=True)
                write_gateway_rpc_frame(writer, segments)
            return
        now = time.monotonic()
        keys = frame.keys
        ttls = frame.ttls
        common_args = frame.common_args
        share_ok = common_args is None or _rpc_args_shared_safe(common_args)
        minfo, iface_id = binding.minfo, binding.iface.interface_id
        gid = binding.gid
        tids, sids = frame.trace_ids, frame.span_ids
        rec = self.silo.spans if tids is not None else None
        futures: list = []
        calls: list = []
        for i in range(frame.n):
            if common_args is not None:
                args = common_args if share_ok else \
                    tuple(codec.deep_copy(a) for a in common_args)
            else:
                args = frame.args_list[i]
            deadline = now + float(ttls[i]) if ttls is not None else None
            fut = loop.create_future() if want else None
            if fut is not None:
                futures.append(fut)
            trace = None
            if tids is not None:
                trace = codec_mod.unpack_rpc_trace(int(tids[i]),
                                                   int(sids[i]))
                if trace is not None and rec is not None:
                    # the gateway-frame hop of a sampled lane's journey
                    rec.event(f"gateway frame {minfo.name}",
                              "gateway.rpc", trace, start=now,
                              client=str(client_id), lanes=frame.n)
            calls.append(_Call(gid(int(keys[i])), minfo, iface_id, args,
                               fut, deadline, client_id, trace))
        gateway.submit_calls(calls)
        if want:
            task = loop.create_task(
                _rpc_reply(self, writer, frame.batch_id, futures))
            task.add_done_callback(lambda t: t.cancelled()
                                   or t.exception())

    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        gateway: Gateway = self.silo.system_targets.get("gateway")
        self._conns.add(writer)
        registered: list = []  # client + observer ids bound to this socket
        # negotiated rpc dictionary: rpc_id → binding (None = announced
        # but unresolvable; its calls answer error result frames)
        rpc_bindings: Dict[int, Optional[_RpcBinding]] = {}
        try:
            hello = await read_gateway_frame(reader)
            if not (isinstance(hello, dict) and hello.get("op") == "hello"):
                raise ValueError("gateway connection must open with HELLO")
            client_id: GrainId = hello["client_id"]

            def deliver(msg: Message) -> None:
                if writer.is_closing():
                    return
                write_gateway_frame(writer, _with_ttl(msg))

            await gateway.connect_client(client_id, deliver, wired=True)
            registered.append(client_id)
            write_gateway_frame(writer, {"op": "welcome",
                                         "silo": str(self.silo.address)})

            while True:
                magic, payload = await read_gateway_payload(reader)
                with self.stage("decode"):
                    frame = decode_gateway_frame(magic, payload)
                self.frames += 1
                if isinstance(frame, Message):
                    with self.stage("submit"):
                        gateway.submit(_rebase_expiration_inbound(frame),
                                       already_wired=True)
                elif isinstance(frame, RpcFrame):
                    self._handle_rpc_calls(gateway, writer, client_id,
                                           rpc_bindings, frame)
                elif isinstance(frame, dict):
                    op = frame.get("op")
                    if op == "rpc_bind":
                        # dictionary negotiation: resolve once, every
                        # later calls frame is int-keyed.  A bad bind
                        # costs an error reply + error results for its
                        # calls, never the connection.
                        rpc_id = frame.get("rpc_id")
                        try:
                            rpc_bindings[rpc_id] = \
                                _resolve_rpc_binding(frame)
                        except Exception as exc:  # noqa: BLE001
                            rpc_bindings[rpc_id] = None
                            write_gateway_frame(writer, {
                                "op": "error", "for": "rpc_bind",
                                "rpc_id": rpc_id, "error": repr(exc)})
                    elif op == "vector_batch":
                        # ONE slab in, ONE slab (of results) out — the
                        # codec's first-class ndarray tokens carry the
                        # tensors; nothing per-message anywhere.  A bad
                        # slab (unknown type, no engine) costs only an
                        # error reply, never the connection.
                        batch_id = frame.get("batch_id")

                        def _reply(f: "asyncio.Future",
                                   _id=batch_id) -> None:
                            if writer.is_closing():
                                return
                            with self.stage("reply"):
                                _write_batch_result(f, _id)

                        def _write_batch_result(f: "asyncio.Future",
                                                _id) -> None:
                            if f.exception() is not None:
                                write_gateway_frame(writer, {
                                    "op": "batch_result", "batch_id": _id,
                                    "error": repr(f.exception())})
                            else:
                                write_gateway_frame(writer, {
                                    "op": "batch_result", "batch_id": _id,
                                    "result": f.result()})

                        try:
                            with self.stage("submit"):
                                fut = gateway.submit_batch(
                                    frame["type"], frame["method"],
                                    frame["keys"], frame["args"],
                                    want_results=frame.get("want_results",
                                                           False))
                        except Exception as exc:  # noqa: BLE001
                            if batch_id is not None:
                                write_gateway_frame(writer, {
                                    "op": "batch_result",
                                    "batch_id": batch_id,
                                    "error": repr(exc)})
                            else:
                                self.silo.logger.warn(
                                    f"gateway: bad vector batch dropped: "
                                    f"{exc!r}", code=2902)
                        else:
                            if fut is not None:
                                fut.add_done_callback(_reply)
                    elif op == "observer":
                        await gateway.register_observer(client_id,
                                                        frame["observer_id"])
                        registered.append(frame["observer_id"])
                        write_gateway_frame(writer, {"op": "ok",
                                                     "for": "observer"})
                    elif op == "unregister":
                        # only ids THIS connection registered — otherwise
                        # one client could sever another's routes
                        if frame["grain_id"] in registered:
                            registered.remove(frame["grain_id"])
                            await gateway.disconnect_client(
                                frame["grain_id"])
                    elif op == "bye":
                        break
                    else:
                        raise ValueError(f"unknown gateway op {op!r}")
        except (asyncio.IncompleteReadError, ConnectionError):
            pass  # client vanished: clean up below (reference:
            #       Gateway.RecordClosedSocket)
        except Exception as exc:  # noqa: BLE001 — hostile/corrupt frames
            # must cost only their own connection, never an unhandled-task
            # traceback (the accept loop is internet-facing)
            self.silo.logger.warn(
                f"gateway connection dropped: {exc!r}", code=2901,
                exc_info=True)
        finally:
            self._conns.discard(writer)
            writer.close()
            for grain_id in registered:
                try:
                    await gateway.disconnect_client(grain_id)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
