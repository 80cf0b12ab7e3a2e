"""Multi-host party runtime: a real transport behind the Channel contract
(DESIGN.md §10).

The whole protocol — training (per-layer ``assign_sync`` -> ``split_infos``
-> batched decrypt, §8) and serving (one ``predict_bits`` round-trip per
host per batch, §9) — already flows through tagged, serializable messages
(``core/tree.py``, ``serving/engine.py``).  This module gives those
messages a wire:

* a **payload codec**: numpy/limb tensors, python-int object arrays
  (Paillier ciphertexts), ints/floats/strs/bytes and nested
  tuples/lists/dicts <-> length-prefixed binary.  No pickle anywhere on
  the wire.
* **framed endpoints**: a length-prefixed TCP socket transport and an
  in-memory loopback with the identical framing (the loopback pumps the
  peer inline — single-threaded, deterministic, still exercising the full
  encode/decode path).
* :class:`TransportChannel` — a :class:`~repro.core.party.Channel` whose
  ``send`` *ships* outgoing frames and whose ``recv`` records incoming
  ones, so each party's ledger converges to the same per-tag byte totals
  as the in-process shared ledger (the oracle).  Actual framed socket
  bytes are tallied separately (``tx_bytes``/``rx_bytes``) so the
  analytic wire model (paper eqs 10/16) can be compared against what the
  socket really moved.
* :class:`PartyProcess` — hosts ONE party per OS process for both
  training (drives the party's :class:`~repro.core.tree.HostRuntime`) and
  serving (a :class:`~repro.serving.engine.PartyBits` evaluator built
  from the host's own reloaded export half).
* :class:`MultiHostRun` — guest-side orchestration: spawn host processes,
  train over the sockets, export per-party halves, serve from the
  reloaded halves.

A forced-2-process run is bit-identical to the in-process ``Channel`` run
with identical per-tag ledgers and round-trip counts (asserted in
``tests/test_transport.py``).
"""

from __future__ import annotations

import os
import random as _random
import socket as _socket
import struct
import threading
import time
from collections import Counter, deque

import numpy as np

from ..analysis import schema as wire
from ..analysis.schema import KIND_CTRL, KIND_PROTO, WireSchemaError
from ..core.party import Channel, Stats
from ..obs import trace as obs_trace
from ..obs.metrics import MetricsRegistry
from ..obs.trace import NULL_TRACER, Tracer

_U32 = struct.Struct("!I")
_I64 = struct.Struct("!q")
_F64 = struct.Struct("!d")


class TransportError(RuntimeError):
    pass


class PeerRestarted(TransportError):
    """A lost peer was re-acquired (respawned or re-dialed), but its
    protocol state for the in-flight unit of work is gone: the caller
    must replay from the last resume boundary (the per-tree snapshot),
    not retry the failed frame."""


class RemoteError(TransportError):
    """The peer ANSWERED — with an application-level error frame.  The
    peer is alive and the connection is fine, so this must bypass both
    the retry/reconnect ladder (retrying a deterministic protocol error
    loops forever) and the serving-mode ``PartyUnavailable`` conversion
    (an answering party is not an unavailable one)."""


# one frame may legitimately carry a whole ciphertext batch, but a frame
# claiming more than this is a corrupt/hostile length prefix — refusing
# it bounds what a single bad u32 can make us allocate
MAX_FRAME_BYTES = 1 << 30


def conformance_check(kind, src, dst, tag, payload) -> None:
    """Opt-in ship-time schema validation (``wire.set_conformance(True)``
    or ``REPRO_WIRE_CONFORMANCE=1``).  A violation is a transport-layer
    refusal — the frame never reaches the socket."""
    if not wire.conformance_enabled():
        return
    try:
        wire.validate(kind, src, dst, tag, payload)
    except WireSchemaError as e:
        raise TransportError(f"wire schema violation: {e}") from e


# ---------------------------------------------------------------------------
# payload codec (no pickle on the wire)
# ---------------------------------------------------------------------------

def _enc_str(out: bytearray, s: str) -> None:
    b = s.encode("utf-8")
    out += _U32.pack(len(b))
    out += b


def _enc_bigint(out: bytearray, x: int) -> None:
    sign = 1 if x < 0 else 0
    raw = abs(x).to_bytes((abs(x).bit_length() + 7) // 8 or 1, "big")
    out += bytes([sign])
    out += _U32.pack(len(raw))
    out += raw


def _encode(obj, out: bytearray) -> None:
    if obj is None:
        out += b"N"
    elif isinstance(obj, (bool, np.bool_)):
        out += (b"T" if obj else b"F")
    elif isinstance(obj, (int, np.integer)):
        x = int(obj)
        if -(2 ** 63) <= x < 2 ** 63:
            out += b"i"
            out += _I64.pack(x)
        else:
            out += b"I"
            _enc_bigint(out, x)
    elif isinstance(obj, (float, np.floating)):
        out += b"f"
        out += _F64.pack(float(obj))
    elif isinstance(obj, str):
        out += b"s"
        _enc_str(out, obj)
    elif isinstance(obj, (bytes, bytearray)):
        out += b"b"
        out += _U32.pack(len(obj))
        out += bytes(obj)
    elif isinstance(obj, tuple):
        out += b"u"
        out += _U32.pack(len(obj))
        for it in obj:
            _encode(it, out)
    elif isinstance(obj, list):
        out += b"l"
        out += _U32.pack(len(obj))
        for it in obj:
            _encode(it, out)
    elif isinstance(obj, dict):
        out += b"d"
        out += _U32.pack(len(obj))
        for k, v in obj.items():
            _encode(k, out)
            _encode(v, out)
    else:
        if not isinstance(obj, np.ndarray) and hasattr(obj, "__array__"):
            obj = np.asarray(obj)       # jax arrays land here (sync point)
        if not isinstance(obj, np.ndarray):
            raise TransportError(f"unserializable payload type "
                                 f"{type(obj).__name__}")
        if obj.dtype == object:
            # Paillier ciphertexts / decrypted ints: python bigints
            out += b"O"
            out += bytes([obj.ndim])
            for d in obj.shape:
                out += _I64.pack(d)
            for x in obj.reshape(-1).tolist():
                if not isinstance(x, int):
                    raise TransportError(
                        f"object arrays may only carry python ints, got "
                        f"{type(x).__name__}")
                _enc_bigint(out, x)
        else:
            out += b"a"
            _enc_str(out, str(obj.dtype))
            out += bytes([obj.ndim])
            for d in obj.shape:
                out += _I64.pack(d)
            out += np.ascontiguousarray(obj).tobytes()


def encode_payload(obj) -> bytes:
    out = bytearray()
    _encode(obj, out)
    return bytes(out)


class _Reader:
    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def take(self, n: int) -> bytes:
        b = self.buf[self.pos: self.pos + n]
        if len(b) != n:
            raise TransportError("truncated payload")
        self.pos += n
        return b

    def u32(self) -> int:
        return _U32.unpack(self.take(4))[0]

    def i64(self) -> int:
        return _I64.unpack(self.take(8))[0]

    def string(self) -> str:
        return self.take(self.u32()).decode("utf-8")

    def bigint(self) -> int:
        sign = self.take(1)[0]
        raw = self.take(self.u32())
        x = int.from_bytes(raw, "big")
        return -x if sign else x


def _decode(r: _Reader):
    t = r.take(1)
    if t == b"N":
        return None
    if t == b"T":
        return True
    if t == b"F":
        return False
    if t == b"i":
        return r.i64()
    if t == b"I":
        return r.bigint()
    if t == b"f":
        return _F64.unpack(r.take(8))[0]
    if t == b"s":
        return r.string()
    if t == b"b":
        return r.take(r.u32())
    if t == b"u":
        return tuple(_decode(r) for _ in range(r.u32()))
    if t == b"l":
        return [_decode(r) for _ in range(r.u32())]
    if t == b"d":
        return {_decode(r): _decode(r) for _ in range(r.u32())}
    if t == b"a":
        dtype = np.dtype(r.string())
        shape = _shape(r)
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        arr = np.frombuffer(r.take(n * dtype.itemsize), dtype=dtype)
        return arr.reshape(shape).copy()
    if t == b"O":
        shape = _shape(r)
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        # each bigint needs >= 6 encoded bytes: bound the object-array
        # allocation by what the buffer could possibly hold BEFORE
        # np.empty, or a forged shape header allocates n*8 bytes for free
        if n * 6 > len(r.buf) - r.pos:
            raise TransportError("object-array shape exceeds payload")
        arr = np.empty(n, dtype=object)
        for i in range(n):
            arr[i] = r.bigint()
        return arr.reshape(shape)
    raise TransportError(f"bad payload type byte {t!r}")


def _shape(r: _Reader) -> tuple:
    shape = tuple(r.i64() for _ in range(r.take(1)[0]))
    if any(d < 0 for d in shape) \
            or int(np.prod(shape, dtype=np.float64)) > 2 ** 62:
        raise TransportError(f"bad array shape {shape}")
    return shape


def decode_payload(buf: bytes):
    r = _Reader(buf)
    try:
        obj = _decode(r)
    except TransportError:
        raise
    except Exception as e:          # noqa: BLE001 -- fuzz contract: any
        # malformed byte stream (bad dtype string, non-utf8, numpy/struct
        # refusals) surfaces as TransportError, never as a random
        # internal exception the framing layer can't classify
        raise TransportError(f"malformed payload: "
                             f"{type(e).__name__}: {e}") from e
    if r.pos != len(buf):
        raise TransportError("trailing bytes in payload")
    return obj


# ---------------------------------------------------------------------------
# framing + endpoints
# ---------------------------------------------------------------------------

def encode_frame(kind: int, src: str, dst: str, tag: str, nbytes: int,
                 payload, payload_bytes: bytes | None = None,
                 seq: int = 0) -> bytes:
    out = bytearray([kind])
    _enc_str(out, src)
    _enc_str(out, dst)
    _enc_str(out, tag)
    out += _I64.pack(int(seq))
    out += _I64.pack(int(nbytes))
    out += (payload_bytes if payload_bytes is not None
            else encode_payload(payload))
    return bytes(out)


def decode_frame(buf: bytes) -> tuple:
    r = _Reader(buf)
    try:
        kind = r.take(1)[0]
        if kind not in (KIND_PROTO, KIND_CTRL):
            raise TransportError(f"bad frame kind byte {kind}")
        src, dst, tag = r.string(), r.string(), r.string()
        seq = r.i64()
        nbytes = r.i64()
    except TransportError:
        raise
    except Exception as e:          # noqa: BLE001
        raise TransportError(f"malformed frame header: "
                             f"{type(e).__name__}: {e}") from e
    payload = decode_payload(buf[r.pos:])
    return kind, src, dst, tag, seq, nbytes, payload


def peek_frame_header(buf: bytes) -> tuple:
    """(kind, src, dst, tag, seq) without touching the payload — what the
    fault-injection layer matches rules against (decoding a multi-MB
    ciphertext batch just to learn its tag would make chaos mode alter
    the timing it is trying to perturb)."""
    r = _Reader(buf)
    kind = r.take(1)[0]
    src, dst, tag = r.string(), r.string(), r.string()
    return kind, src, dst, tag, r.i64()


class SocketEndpoint:
    """Length-prefixed frames over one TCP connection (TCP_NODELAY: the
    protocol is strict request/reply, Nagle only adds latency)."""

    def __init__(self, sock: _socket.socket):
        sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        self.sock = sock
        self.dead = False

    def send_bytes(self, frame: bytes) -> None:
        if self.dead:
            raise TransportError("endpoint is dead (mid-frame timeout): "
                                 "reconnect before sending")
        try:
            self.sock.sendall(_U32.pack(len(frame)) + frame)
        except OSError as e:
            raise TransportError(f"send failed: {e}") from e

    def _read_exact(self, n: int) -> bytes:
        buf = bytearray(n)
        view = memoryview(buf)
        got = 0
        while got < n:
            try:
                r = self.sock.recv_into(view[got:], n - got)
            except _socket.timeout:
                raise
            except OSError as e:
                raise TransportError(f"recv failed: {e}") from e
            if r == 0:
                raise TransportError("peer closed the connection")
            got += r
        return bytes(buf)

    def recv_bytes(self, timeout: float | None = None) -> bytes:
        if self.dead:
            raise TransportError("endpoint is dead (mid-frame timeout): "
                                 "reconnect before receiving")
        try:
            self.sock.settimeout(timeout)
        except OSError as e:        # closed under us (chaos / supervisor)
            raise TransportError(f"recv failed: {e}") from e
        try:
            n = _U32.unpack(self._read_exact(4))[0]
            if n > MAX_FRAME_BYTES:
                self.dead = True            # prefix is garbage: framing lost
                self.close()
                raise TransportError(f"frame length {n} exceeds "
                                     f"{MAX_FRAME_BYTES} (corrupt prefix)")
            return self._read_exact(n)
        except _socket.timeout as e:
            # the timeout may have fired AFTER the length prefix (or part
            # of the body) was consumed: the stream is mid-frame, and the
            # next recv would decode body bytes as a length prefix.  A
            # timed-out endpoint is dead — callers must reconnect.
            self.dead = True
            self.close()
            raise TransportError(f"recv timed out after {timeout}s "
                                 f"(endpoint closed: stream may be "
                                 f"mid-frame)") from e

    def poll(self) -> bool:
        import select
        return bool(select.select([self.sock], [], [], 0)[0])

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class LoopbackEndpoint:
    """In-memory endpoint with the same framed interface.  ``on_deliver``
    (when set on the *receiving* end) is invoked after each delivery —
    the inline pump that lets a PartyProcess handle frames synchronously
    inside the sender's call, single-threaded and deterministic."""

    def __init__(self):
        self.inbox: deque = deque()
        self.peer: "LoopbackEndpoint | None" = None
        self.on_deliver = None
        self.closed = False

    @classmethod
    def pair(cls) -> tuple:
        a, b = cls(), cls()
        a.peer, b.peer = b, a
        return a, b

    def send_bytes(self, frame: bytes) -> None:
        if self.peer is None or self.peer.closed:
            raise TransportError("loopback peer closed")
        self.peer.inbox.append(frame)
        if self.peer.on_deliver is not None:
            self.peer.on_deliver()

    def recv_bytes(self, timeout: float | None = None) -> bytes:
        if not self.inbox:
            raise TransportError("loopback recv on empty inbox (protocol "
                                 "desync: no pending frame)")
        return self.inbox.popleft()

    def poll(self) -> bool:
        return bool(self.inbox)

    def close(self) -> None:
        self.closed = True


# ---------------------------------------------------------------------------
# the channel over a transport
# ---------------------------------------------------------------------------

class _BrokerInbox:
    """Async receive queue for one peer (DESIGN.md §12).

    A broker thread drains the peer's endpoint continuously — every frame
    is read off the socket, decoded, mirrored into the ledger and deduped
    by seq the moment it ARRIVES, then parked in a per-tag inbox.  The
    protocol thread consumes from the inboxes instead of the socket, so a
    pipelined guest's ``enc_gh`` for round r+1 is accepted (bytes moved,
    payload decoded) while the party is still deep in round r's histogram
    compute.  Consumption is arrival-ordered by default (``pop()``); a
    caller that knows its tag may pull past queued frames of other tags
    (``pop(tag=...)``) — ledger convergence is unaffected because the
    mirror happens at ingest, not at consumption.

    A transport failure poisons the inbox: the pending error re-raises on
    every subsequent pop until :meth:`TransportChannel.start_broker` is
    called again over a fresh endpoint (the host re-dial loop does this).
    """

    def __init__(self, channel: "TransportChannel", src: str):
        self.channel = channel
        self.src = src
        self.cond = threading.Condition()
        self.inbox: dict = {}       # tag -> deque of ingested frames
        self.order: deque = deque()  # tags in arrival order
        self.err: BaseException | None = None
        self.stop = False
        self.thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"broker-{channel.party}-{src}")

    def _run(self) -> None:
        ch = self.channel
        while not self.stop:
            try:
                ep = ch.peers.get(self.src)
                if ep is None:
                    raise TransportError(f"{ch.party}: no endpoint for "
                                         f"{self.src!r}")
                t0 = time.perf_counter()
                frame = ep.recv_bytes(ch.timeout)
                got = ch._ingest(frame, t0)
            except BaseException as e:          # noqa: BLE001 -- poison:
                # the protocol thread re-raises this from its next pop
                with self.cond:
                    if not self.stop:
                        self.err = e
                    self.cond.notify_all()
                return
            if got is None:
                continue                        # skimmed / deduped
            with self.cond:
                self.inbox.setdefault(got[3], deque()).append(got)
                self.order.append(got[3])
                depth = len(self.order)
                self.cond.notify_all()
            ch.metrics.gauge("broker_depth").observe(depth)
            if ch.tracer.enabled:
                ch.tracer.instant("broker_park", cat="transport",
                                  src=self.src, tag=got[3], depth=depth)

    def _waited(self, got, t_ns: int):
        """Emit the protocol thread's park-to-pop wait as a span."""
        tr = self.channel.tracer
        if tr.enabled:
            tr.complete("broker_pop", t_ns, time.perf_counter_ns() - t_ns,
                        cat="transport", src=self.src, tag=got[3])
        return got

    def pop(self, tag: str | None = None, timeout: float | None = None):
        """Next ingested frame — arrival order, or first frame of ``tag``."""
        t_ns = (time.perf_counter_ns() if self.channel.tracer.enabled
                else 0)
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self.cond:
            while True:
                if tag is None:
                    if self.order:
                        return self._waited(
                            self.inbox[self.order.popleft()].popleft(), t_ns)
                else:
                    q = self.inbox.get(tag)
                    if q:
                        self.order.remove(tag)   # earliest entry of tag
                        return self._waited(q.popleft(), t_ns)
                if self.err is not None:
                    raise self.err
                budget = (None if deadline is None
                          else deadline - time.monotonic())
                if budget is not None and budget <= 0:
                    raise TransportError(
                        f"{self.channel.party}: broker recv of "
                        f"{tag or 'any'!r} from {self.src} timed out "
                        f"after {timeout}s")
                self.cond.wait(budget)

    def try_pop(self):
        with self.cond:
            if self.order:
                return self.inbox[self.order.popleft()].popleft()
            if self.err is not None:
                raise self.err
            return None

    def pending(self, tag: str) -> int:
        with self.cond:
            return len(self.inbox.get(tag, ()))


class TransportChannel(Channel):
    """The Channel contract over real endpoints.

    ``send`` keeps the exact in-process accounting (same tags, same
    analytic nbytes) and additionally ships the frame when ``dst`` is a
    remote peer; ``recv`` decodes one incoming frame and records it in
    the ledger, so a 2-party conversation yields the same per-tag ledger
    on each side as the single in-process ledger does.  Framed bytes that
    actually crossed the transport are counted per tag in
    ``tx_bytes``/``rx_bytes`` (control frames included): the gap between
    those and the ledger is the protocol-vs-socket overhead the
    transport benchmark reports.
    """

    def __init__(self, party: str, peers: dict, timeout: float = 600.0,
                 max_retries: int = 2, retry_backoff: float = 0.05):
        super().__init__()
        self.party = party
        self.peers = peers
        self.timeout = timeout
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self.tx_bytes = Counter()       # tag -> framed bytes shipped
        self.rx_bytes = Counter()       # tag -> framed bytes received
        self._enc_memo = (object(), b"")    # one-slot broadcast memo
                                            # (sentinel: matches nothing)
        # sequence numbers: every PROTOCOL frame carries a per-(peer, tag)
        # seq so the receiver can count retransmitted/replayed frames in
        # its mirrored ledger exactly once (DESIGN.md §11)
        self.send_seq = Counter()       # (dst, tag) -> last seq sent
        self.last_seen = Counter()      # (src, tag) -> last seq mirrored
        # reconnect hook: called with the peer name after a failed
        # send/recv; reestablishes the endpoint (guest: accept+respawn —
        # raises PeerRestarted to force a tree replay; host: re-dial —
        # returns, and the retried op resumes against the new socket)
        self.reconnect = None
        self.on_rtt = None              # (peer, tag, seconds) per recv
        self.on_ctrl = None             # skim hook for async control
                                        # frames (supervisor hb_ack)
        self.serving_mode = False       # typed PartyUnavailable errors
        self._send_locks: dict = {}     # per-peer: supervisor thread pings
                                        # must not interleave frame bytes
                                        # with training-thread sends
        self._brokers: dict = {}        # src -> _BrokerInbox (async recv)
        self._mirror_lock = threading.Lock()    # rx/tx byte counters are
                                        # touched by broker + send threads
        self._jitter = _random.Random(len(party) * 2654435761 + 17)
        # transport-plane instruments (per-tag RTT histograms, broker
        # queue depth, retry count) — separate from Stats.metrics, which
        # holds TRAINING timers; both surface through the status frame
        self.metrics = MetricsRegistry()

    def _send_lock(self, dst: str):
        lock = self._send_locks.get(dst)
        if lock is None:
            lock = self._send_locks[dst] = threading.Lock()
        return lock

    # -- retry ----------------------------------------------------------
    def _with_retry(self, op, peer: str):
        """Run ``op`` with exponential backoff + jitter; between attempts
        let the reconnect hook reestablish the peer's endpoint."""
        delay = self.retry_backoff
        for attempt in range(self.max_retries + 1):
            try:
                return op()
            except (PeerRestarted, RemoteError):
                raise               # replay / surface: never blind-retry
            except TransportError as e:
                if self.serving_mode and peer.startswith("host"):
                    from ..core.party import PartyUnavailable
                    raise PartyUnavailable(peer, str(e)) from e
                if attempt == self.max_retries:
                    raise
                self.metrics.counter("transport_retries").add()
                if self.tracer.enabled:
                    self.tracer.instant("retry", cat="transport", peer=peer,
                                        attempt=attempt + 1,
                                        error=type(e).__name__)
                if self.reconnect is not None:
                    self.reconnect(peer)    # may raise PeerRestarted
                time.sleep(delay + self._jitter.uniform(0.0, delay / 2))
                delay *= 2

    # -- outgoing -------------------------------------------------------
    def send(self, src: str, dst: str, tag: str, payload, nbytes: int):
        super().send(src, dst, tag, payload, nbytes)
        if dst != self.party:
            self.send_seq[(dst, tag)] += 1
            seq = self.send_seq[(dst, tag)]
            self._with_retry(
                lambda: self._ship(KIND_PROTO, src, dst, tag, nbytes,
                                   payload, seq), dst)
        return payload

    def control_send(self, dst: str, tag: str, payload) -> None:
        self._with_retry(
            lambda: self._ship(KIND_CTRL, self.party, dst, tag, 0, payload),
            dst)

    def _ship(self, kind, src, dst, tag, nbytes, payload, seq=0) -> None:
        ep = self.peers.get(dst)
        if ep is None:
            raise TransportError(f"{self.party}: no endpoint for {dst!r}")
        conformance_check(kind, src, dst, tag, payload)
        # broadcast memo: the guest sends the SAME payload object to every
        # host back to back (enc_gh ciphertext batch, layer plans) — encode
        # it once, not once per destination (the enc_gh encode includes a
        # jax device sync)
        memo_obj, payload_bytes = self._enc_memo
        if payload is not memo_obj:
            payload_bytes = encode_payload(payload)
            self._enc_memo = (payload, payload_bytes)
        frame = encode_frame(kind, src, dst, tag, nbytes, None,
                             payload_bytes=payload_bytes, seq=seq)
        t_ns = time.perf_counter_ns() if self.tracer.enabled else 0
        with self._send_lock(dst):
            ep.send_bytes(frame)
        with self._mirror_lock:
            self.tx_bytes[tag] += len(frame) + 4    # + length prefix
        if self.tracer.enabled:
            # physical view (framed bytes incl. prefix): cat "transport",
            # never "wire" — the ledger audit must not see frame overhead
            self.tracer.complete("ship", t_ns,
                                 time.perf_counter_ns() - t_ns,
                                 cat="transport", dst=dst, tag=tag,
                                 seq=int(seq), nbytes=len(frame) + 4)
        # a retried send re-enters here through peers[dst] (possibly a
        # fresh endpoint) with the SAME seq: the receiver dedupes

    # -- incoming -------------------------------------------------------
    def _ingest(self, frame: bytes, t0: float):
        """Decode, account, dedup and mirror ONE incoming frame.  Returns
        the ``(kind, src, dst, tag, payload)`` tuple, or None when the
        frame was swallowed (skimmed control ack, deduped retransmission).
        Shared by the synchronous read path and the broker thread — both
        must apply identical mirror/dedup semantics or the converged
        per-tag ledgers drift between brokered and unbrokered parties."""
        kind, fsrc, fdst, tag, seq, nbytes, payload = decode_frame(frame)
        with self._mirror_lock:
            self.rx_bytes[tag] += len(frame) + 4
        if kind == KIND_PROTO:
            # per-tag round-trip (recv-start to frame decoded) — feeds
            # the status snapshot alongside the straggler policy's view
            self.metrics.histogram(f"rtt:{tag}").observe(
                time.perf_counter() - t0)
        if self.tracer.enabled:
            self.tracer.instant("recv", cat="transport", src=fsrc, tag=tag,
                                seq=int(seq), nbytes=len(frame) + 4)
        if self.on_rtt is not None and kind == KIND_PROTO:
            self.on_rtt(fsrc, tag, time.perf_counter() - t0)
        if kind == KIND_CTRL and tag == wire.ERROR:
            # a peer's dying words: surface its actual failure instead
            # of a tag mismatch now / 'peer closed' later
            raise RemoteError(f"peer {fsrc} failed: {payload}")
        if kind == KIND_CTRL and self.on_ctrl is not None \
                and self.on_ctrl(fsrc, tag, payload):
            return None             # skimmed (liveness ack): not ours
        if kind == KIND_PROTO:
            if seq <= self.last_seen[(fsrc, tag)]:
                # retransmission of a frame already mirrored.  Counted
                # once; and — except for enc_gh, the idempotent tree
                # replay anchor — not re-delivered either, or a
                # duplicated chosen_sid would corrupt the frontier.
                if tag != wire.ENC_GH:
                    return None
            else:
                self.last_seen[(fsrc, tag)] = seq
                # mirror the sender's ledger entry (analytic nbytes
                # travels in the frame header) so each side's per-tag
                # totals converge to the in-process shared ledger
                Channel.send(self, fsrc, fdst, tag, payload, nbytes)
        return kind, fsrc, fdst, tag, payload

    def _read(self, src: str, timeout: float | None = None):
        br = self._brokers.get(src)
        if br is not None:
            return br.pop(timeout=self.timeout if timeout is None
                          else timeout)
        def op():
            return self._read_once(src, timeout)
        return self._with_retry(op, src)

    def _read_once(self, src: str, timeout: float | None = None):
        while True:
            ep = self.peers.get(src)
            if ep is None:
                raise TransportError(f"{self.party}: no endpoint for "
                                     f"{src!r}")
            t0 = time.perf_counter()
            frame = ep.recv_bytes(self.timeout if timeout is None
                                  else timeout)
            got = self._ingest(frame, t0)
            if got is not None:
                return got

    # -- async broker (pipelined mode, DESIGN.md §12) -------------------
    def start_broker(self, src: str) -> None:
        """Switch receives from ``src`` to an async broker: a reader
        thread drains the endpoint continuously into per-tag inboxes so
        frames are accepted the moment they arrive — a pipelined guest's
        next-round ``enc_gh`` no longer waits in kernel buffers behind
        the current round's compute.  Idempotent per connection: calling
        it again (after a re-dial swapped ``peers[src]``) replaces the
        poisoned broker with a fresh one."""
        old = self._brokers.pop(src, None)
        if old is not None:
            old.stop = True
        br = _BrokerInbox(self, src)
        self._brokers[src] = br
        br.thread.start()

    def stop_broker(self, src: str | None = None) -> None:
        for key in ([src] if src is not None else list(self._brokers)):
            br = self._brokers.pop(key, None)
            if br is not None:
                br.stop = True
                with br.cond:
                    br.cond.notify_all()

    def broker(self, src: str) -> "_BrokerInbox | None":
        return self._brokers.get(src)

    def recv(self, src: str, tag: str, timeout: float | None = None):
        """Blocking receive of one PROTOCOL frame from ``src``; the tag
        must match (the protocol is strict request/reply — anything else
        is a desync worth crashing on).  Over a broker the match is a
        *selection*: queued frames of other tags (the pipelined next
        round's ``enc_gh``) stay parked instead of tripping the desync
        check."""
        br = self._brokers.get(src)
        if br is not None:
            kind, _, _, ftag, payload = br.pop(
                tag=tag, timeout=self.timeout if timeout is None
                else timeout)
        else:
            kind, _, _, ftag, payload = self._read(src, timeout)
        if kind != KIND_PROTO or ftag != tag:
            raise TransportError(f"{self.party}: expected protocol frame "
                                 f"{tag!r} from {src}, got "
                                 f"{'ctrl' if kind else 'proto'}:{ftag!r}")
        return payload

    def control_recv(self, src: str, tag: str):
        br = self._brokers.get(src)
        if br is not None:
            kind, _, _, ftag, payload = br.pop(tag=tag,
                                               timeout=self.timeout)
        else:
            kind, _, _, ftag, payload = self._read(src)
        if kind != KIND_CTRL or ftag != tag:
            raise TransportError(f"{self.party}: expected control frame "
                                 f"{tag!r} from {src}, got "
                                 f"{'ctrl' if kind else 'proto'}:{ftag!r}")
        return payload

    def recv_any(self, src: str) -> tuple:
        """(kind, tag, payload) of the next frame from ``src`` — the
        PartyProcess serve loop."""
        kind, _, _, tag, payload = self._read(src)
        return kind, tag, payload

    def try_recv_any(self, src: str):
        br = self._brokers.get(src)
        if br is not None:
            got = br.try_pop()
            if got is None:
                return None
            kind, _, _, tag, payload = got
            return kind, tag, payload
        ep = self.peers.get(src)
        if ep is None or not ep.poll():
            return None
        return self.recv_any(src)

    # -- resume boundaries ---------------------------------------------
    def snapshot(self) -> dict:
        """Accounting + sequence state at a tree boundary.  Restoring
        rolls BOTH back, so a replayed tree re-sends frames with the same
        seqs (the peer, also rolled back, counts them fresh) — ledgers
        converge to the fault-free oracle.  ``tx_bytes``/``rx_bytes`` are
        deliberately NOT rolled back: they count what the socket really
        moved, retries included (that gap IS the cost of the fault)."""
        snap = super().snapshot()
        snap["send_seq"] = self.send_seq.copy()
        snap["last_seen"] = self.last_seen.copy()
        return snap

    def restore(self, snap: dict) -> None:
        super().restore(snap)
        self.send_seq = snap["send_seq"].copy()
        self.last_seen = snap["last_seen"].copy()

    def state_dump(self) -> dict:
        """The channel state a party must persist to rejoin a run after
        a process death: full ledger + seq counters, codec-serializable
        (tuple keys survive the payload codec round-trip)."""
        return {"ledger": [tuple(e) for e in self.ledger],
                "totals": dict(self.totals), "msgs": dict(self.msgs),
                "coll_ledger": [tuple(e) for e in self.coll_ledger],
                "coll_totals": dict(self.coll_totals),
                "coll_msgs": dict(self.coll_msgs),
                "send_seq": dict(self.send_seq),
                "last_seen": dict(self.last_seen)}

    def state_load(self, d: dict) -> None:
        self.ledger = [tuple(e) for e in d["ledger"]]
        self.totals = Counter(d["totals"])
        self.msgs = Counter(d["msgs"])
        self.coll_ledger = [tuple(e) for e in d["coll_ledger"]]
        self.coll_totals = Counter(d["coll_totals"])
        self.coll_msgs = Counter(d["coll_msgs"])
        self.send_seq = Counter(d["send_seq"])
        self.last_seen = Counter(d["last_seen"])

    def drain(self, src: str, until_ctrl: str | None = None,
              timeout: float = 1.0) -> int:
        """Discard pending frames from ``src`` WITHOUT mirroring them —
        the aborted attempt's in-flight replies; the rolled-back snapshot
        already forgot their sends.  With ``until_ctrl``, block (up to
        ``timeout`` per frame) until that control tag arrives — the
        resync barrier: a host answers ``resync`` only after flushing
        every previous reply into the stream, so everything drained
        before the ack is provably stale."""
        ep = self.peers.get(src)
        n = 0
        while ep is not None:
            if until_ctrl is None and not ep.poll():
                break
            frame = ep.recv_bytes(timeout)
            kind, _, _, tag, _, _, payload = decode_frame(frame)
            with self._mirror_lock:
                self.rx_bytes[tag] += len(frame) + 4
            if kind == KIND_CTRL and tag == wire.ERROR:
                raise TransportError(f"peer {src} failed: {payload}")
            if kind == KIND_CTRL and tag == until_ctrl:
                break
            n += 1
        return n

    # -- socket accounting ---------------------------------------------
    def reset_accounting(self) -> None:
        super().reset_accounting()
        with self._mirror_lock:
            self.tx_bytes.clear()
            self.rx_bytes.clear()
        self.send_seq.clear()
        self.last_seen.clear()
        self.metrics.clear()        # per-fit, like the byte counters

    @property
    def total_tx_bytes(self) -> int:
        with self._mirror_lock:
            return sum(self.tx_bytes.values())

    @property
    def total_rx_bytes(self) -> int:
        with self._mirror_lock:
            return sum(self.rx_bytes.values())

    def socket_summary(self) -> dict:
        with self._mirror_lock:
            tags = sorted(set(self.tx_bytes) | set(self.rx_bytes))
            return {t: {"tx": self.tx_bytes[t], "rx": self.rx_bytes[t]}
                    for t in tags}

    def close(self) -> None:
        self.stop_broker()
        for ep in self.peers.values():
            ep.close()


# ---------------------------------------------------------------------------
# guest-side handles
# ---------------------------------------------------------------------------

class RemoteHostHandle:
    """What the grower sees for a host living in another process: the
    guest's ``channel.send`` already shipped every guest->host message, so
    ``deliver`` is a no-op and ``collect`` blocks on the reply frame.
    Mirror of the in-process ``HostRuntime`` handle surface."""

    def __init__(self, channel: TransportChannel, hid: int):
        self.channel = channel
        self.hid = hid

    @property
    def table(self) -> dict:
        return {}           # host-private; never enters the guest process

    def bind(self, params, cipher, channel, stats) -> None:
        pass

    def deliver(self, tag: str, payload) -> None:
        pass

    def collect(self, tag: str):
        return self.channel.recv(f"host{self.hid}", tag)


class RemoteServingHost:
    """Serving-side handle: the host's PartyProcess computes its packed
    decision bits and answers the guest's ``predict_req``.

    ``serve_timeout`` bounds the reply wait: with ``serving_mode`` set on
    the channel, a down/late host surfaces as a typed
    :class:`~repro.core.party.PartyUnavailable` for THIS batch — never a
    hang, and never a partial-bits answer (the engine discards the whole
    batch on any party failure)."""

    def __init__(self, channel: TransportChannel, hid: int, k: int,
                 serve_timeout: float | None = None):
        self.channel = channel
        self.hid = hid
        self.k = int(k)
        self.serve_timeout = serve_timeout

    def predict_bits(self):
        return self.channel.recv(f"host{self.hid}", wire.PREDICT_BITS,
                                 self.serve_timeout)


# ---------------------------------------------------------------------------
# the party process (host side)
# ---------------------------------------------------------------------------

def _strip_private_key(cipher):
    """Reduce a cipher object to what a passive host may hold.

    The repro's cipher classes bundle keygen and BOTH key halves for the
    in-process simulation (key distribution here is a simulation
    shortcut: the host derives the shared parameters from the run config
    instead of a key-exchange handshake).  A host party only ever needs
    the public/evaluation surface — modulus, Barrett context, lazy
    reduce/sub, compress shifts — so the private material is deleted the
    moment the object exists: any host-side code path that reached for
    decrypt (or the affine scheme's symmetric encrypt) dies with an
    AttributeError instead of silently voiding the privacy boundary.
    ``plain`` is the keyless debugging cipher; nothing to strip.
    """
    for attr in ("T_dec", "T_enc", "a_inv_int", "a_int", "_lam", "_mu"):
        if hasattr(cipher, attr):
            delattr(cipher, attr)
    return cipher


class PartyProcess:
    """One host party, driven entirely by decoded frames.

    Training frames (``enc_gh`` / ``assign_sync`` / ``chosen_sid``) run the
    same :class:`~repro.core.tree.HostRuntime` handlers the in-process
    simulation runs — replies leave through this party's
    :class:`TransportChannel`.  Serving is set up by a ``serve_setup``
    control frame: the host builds its :class:`HostHalf` from its private
    per-tree tables + the guest-published bit-column key order, exports it
    to ``export_dir``, RELOADS it, and answers ``predict_req`` from the
    reloaded half (the per-party export is the process boundary).
    """

    def __init__(self, hid: int, params, X_host, channel: TransportChannel,
                 export_dir: str | None = None,
                 state_dir: str | None = None,
                 own_process: bool = False):
        from ..core.binning import (BinnedData, bin_features,
                                    bin_features_stream)
        from ..data.pipeline import RowBlocks
        self.hid = hid
        self.params = params
        self.channel = channel
        self.export_dir = export_dir
        self.state_dir = state_dir
        self.stats = Stats()
        if getattr(params, "trace", False):
            # one tracer per party; in a spawned host process it is ALSO
            # installed as the process default so chaos endpoints — which
            # wrap the transport before this channel existed — land their
            # injection instants here.  A loopback party shares the
            # GUEST's process, so it must never touch the default: the
            # enabled tracer would outlive this run and leak into later
            # trace=False fits in the same process (chaos is never
            # injected over loopback, so nothing is lost).
            self.tracer = Tracer(f"host{hid}")
            channel.tracer = self.tracer
            if own_process:
                obs_trace.set_default(self.tracer)
        else:
            # inherit whatever the embedder attached (NULL by default) —
            # never clobber a benchmark's process-default tracer
            self.tracer = channel.tracer
        # out-of-core sources (§13): a pre-binned BinnedData (pickles lean —
        # no device buffers — so it crosses the spawn boundary) or a chunked
        # RowBlocks source skip the monolithic fit; raw serving rows then
        # arrive per batch via the serve_data frame
        if isinstance(X_host, BinnedData):
            self.data = X_host
            self.X_serve = np.zeros((0, self.data.bins.shape[1]))
        elif isinstance(X_host, RowBlocks):
            self.data = bin_features_stream(X_host, params.n_bins,
                                            sparse=params.sparse,
                                            use_pallas=params.use_pallas)
            self.X_serve = np.zeros((0, self.data.bins.shape[1]))
        else:
            self.data = bin_features(np.asarray(X_host), params.n_bins,
                                     sparse=params.sparse,
                                     use_pallas=params.use_pallas)
            self.X_serve = np.asarray(X_host)
        self.cipher = None
        self.hr = None              # current tree's HostRuntime
        self.tables: dict = {}      # tree_idx -> {nid: (fid, bid)}
        self.server = None          # PartyBits after serve_setup
        self._serve_k = 0
        self._current_tree = None   # in-flight (possibly partial) tree
        self._complete: set = set()    # trees whose table is final
        self._tree_snaps: dict = {}    # tree -> channel snapshot at its
                                       # enc_gh boundary (replay rollback)
        self._tree_span: dict = {}     # base tree -> member count (round-
                                       # forest: one enc_gh covers k trees)
        # pipelined mode: a future tree's enc_gh arrives while the current
        # tree is still splitting — its runtime is built eagerly (cipher-
        # texts land device-resident) and staged here until the first
        # assign_sync that references the new tree activates it
        from ..core.frontier import FrontierBuffer
        self._staged = FrontierBuffer()
        self.staged_activations = 0    # trees that went through the
                                       # stage->activate path (pipelining
                                       # actually overlapped; test hook)
        # handle() runs from the serve loop AND (loopback pipelining) from
        # the guest's encrypt-pump thread via on_deliver: one frame's
        # protocol mutation at a time, in arrival order
        self._handle_lock = threading.RLock()
        self._load_state()

    # -- durable state (what a party persists to rejoin, DESIGN.md §11) -
    def _state_path(self) -> str | None:
        return (os.path.join(self.state_dir, f"host{self.hid}.state")
                if self.state_dir else None)

    def _persist_state(self) -> None:
        """Written at every enc_gh boundary: completed split tables +
        the channel's accounting/seq state AS OF that boundary.  A
        respawned process reloads this, the guest replays the one
        in-flight tree, and both ledgers converge — without it a crashed
        host would have to replay the whole run."""
        path = self._state_path()
        if path is None:
            return
        state = {"complete": sorted(self._complete),
                 "tables": {int(t): {int(nid): (int(f), int(b))
                                     for nid, (f, b) in
                                     self.tables[t].items()}
                            for t in self._complete},
                 "channel": self.channel.state_dump(),
                 "stats": self.stats.as_dict()}
        tmp = path + ".tmp"
        os.makedirs(os.path.dirname(tmp) or ".", exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(encode_payload(state))
        os.replace(tmp, path)       # atomic: a crash mid-write keeps the
                                    # previous boundary's state

    def _load_state(self) -> None:
        path = self._state_path()
        if path is None or not os.path.exists(path):
            return
        with open(path, "rb") as f:
            state = decode_payload(f.read())
        self._complete = set(int(t) for t in state["complete"])
        self.tables = {int(t): {int(nid): (int(f), int(b))
                                for nid, (f, b) in tbl.items()}
                       for t, tbl in state["tables"].items()}
        self.channel.state_load(state["channel"])
        self.stats = Stats()
        self.stats.merge_counts(state["stats"])

    def resume_info(self) -> dict:
        """Handshake payload: how far this party's durable state reaches
        (the guest resumes from the MINIMUM across parties)."""
        return {"n_complete": len(self._complete),
                "last_seen": {f"{s}|{t}": int(v) for (s, t), v
                              in self.channel.last_seen.items()},
                "send_seq": {f"{d}|{t}": int(v) for (d, t), v
                             in self.channel.send_seq.items()}}

    # -- frame dispatch -------------------------------------------------
    def serve_forever(self) -> None:
        while True:
            kind, tag, payload = self.channel.recv_any("guest")
            try:
                cont = self.handle(kind, tag, payload)
            except Exception as e:             # noqa: BLE001
                # ship the real failure to the guest before dying: the
                # alternative is an opaque 'peer closed the connection'
                # on the guest's next recv
                try:
                    self.channel.control_send(
                        "guest", wire.ERROR,
                        f"host{self.hid} {type(e).__name__}: {e}")
                except Exception:              # noqa: BLE001
                    pass
                raise
            if not cont:
                return

    def pump(self) -> None:
        """Drain pending frames (loopback inline mode)."""
        with self._handle_lock:
            while True:
                got = self.channel.try_recv_any("guest")
                if got is None:
                    return
                self.handle(*got)

    def handle(self, kind: int, tag: str, payload) -> bool:
        with self._handle_lock:
            return self._handle(kind, tag, payload)

    def _handle(self, kind: int, tag: str, payload) -> bool:
        if kind == KIND_CTRL:
            return self._control(tag, payload)
        if tag == wire.ENC_GH:
            self._begin_tree(payload)
        elif tag in (wire.ASSIGN_SYNC, wire.CHOSEN_SID):
            tree = (payload.get("tree") if isinstance(payload, dict)
                    else None)
            if (tree is not None and self._current_tree is not None
                    and int(tree) != self._current_tree):
                # first frame of the NEXT pipelined tree: the staged
                # runtime takes over, the previous tree is final
                self._activate_tree(int(tree))
            self.hr.deliver(tag, payload)
            self.hr._outbox.clear()     # replies already shipped
        elif tag == wire.PREDICT_REQ:
            self._predict(payload)
        else:
            raise TransportError(f"host{self.hid}: unknown protocol tag "
                                 f"{tag!r}")
        return True

    # -- training -------------------------------------------------------
    def _complete_tree(self, base: int) -> None:
        """The tree (or whole round-forest span) rooted at ``base`` saw
        its last update: it joins the durable floor a respawn can resume
        from."""
        for t in range(base, base + self._tree_span.get(base, 1)):
            self._complete.add(t)

    def _build_runtime(self, payload):
        """Fresh engine + HostRuntime adopting this enc_gh batch — the
        ciphertexts land device-resident here.  Round-forest batches
        (``forest`` = k > 1) additionally get per-member split-table
        mirrors so serving export sees k member trees with local nids."""
        from ..core.histogram import CipherHistogram
        from ..core.tree import HostRuntime
        tree = int(payload["tree"])
        k = int(payload.get("forest", 0) or 0)
        if self.cipher is None:
            from ..core.boosting import cipher_kwargs
            from ..core.he import get_cipher
            self.cipher = _strip_private_key(
                get_cipher(self.params.cipher,
                           **cipher_kwargs(self.params)))
        engine = CipherHistogram(self.cipher, self.params.n_bins,
                                 sparse=self.params.sparse,
                                 use_pallas=self.params.use_pallas,
                                 stats=self.stats, tracer=self.tracer)
        hr = HostRuntime(hid=self.hid, data=self.data, engine=engine)
        hr.bind(self.params, self.cipher, self.channel, self.stats)
        hr.deliver(wire.ENC_GH, payload)
        if k > 1:
            sinks = {m: {} for m in range(k)}
            hr.table_sinks = sinks
            for m in range(k):
                self.tables[tree + m] = sinks[m]
            self._tree_span[tree] = k
        else:
            self.tables[tree] = hr.table
            self._tree_span[tree] = 1
        return hr

    def _begin_tree(self, payload) -> None:
        tree = int(payload["tree"])
        if isinstance(payload, dict) and int(payload.get("blk", 0) or 0) > 0:
            # later block of a chunked enc_gh (DESIGN.md §13): route to the
            # runtime already assembling this tree — active, or
            # pipelined-staged — with NO boundary actions; blk 0 was the
            # tree boundary (snapshot/persist/stage happened there).  A
            # block for a tree we are not assembling is a stale
            # re-delivery after a replay restart (the replay anchor
            # re-ships from blk 0): drop it.
            if self._staged.staged(tree):
                self._staged.peek(tree).deliver(wire.ENC_GH, payload)
            elif self._current_tree == tree and self.hr is not None:
                self.hr.deliver(wire.ENC_GH, payload)
            return
        if (getattr(self.params, "pipeline", False)
                and self._current_tree is not None
                and self._current_tree != tree):
            # pipelined prefetch: this tree's ciphertexts arrived while
            # the current tree is still splitting.  Build its runtime
            # eagerly — wire+decode+device placement hidden behind the
            # in-flight tree's compute — but do NOT disturb the active
            # state; the first assign_sync naming this tree activates it.
            self._staged.stage(tree, self._build_runtime(payload))
            return
        if self._current_tree is not None and self._current_tree != tree:
            # the previous tree's table saw its last update: it is now
            # part of the durable floor a respawn can resume from
            self._complete_tree(self._current_tree)
        if tree in self._tree_snaps:
            # a REPLAYED tree (the guest rolled back to this boundary
            # after a fault): roll our accounting and seq counters back
            # too, so the replay's frames are counted fresh, exactly once
            self.channel.restore(self._tree_snaps[tree])
            for t in range(tree, tree + self._tree_span.get(tree, 1)):
                self._complete.discard(t)
        self._current_tree = tree
        self._persist_state()       # durable state AS OF this boundary
        self._tree_snaps[tree] = self.channel.snapshot()
        self.hr = self._build_runtime(payload)

    def _activate_tree(self, tree: int) -> None:
        if not self._staged.staged(tree):
            raise TransportError(
                f"host{self.hid}: assign_sync references tree {tree} but "
                f"no staged enc_gh (current {self._current_tree}) — "
                f"protocol desync")
        if self._current_tree is not None:
            self._complete_tree(self._current_tree)
        self._current_tree = tree
        self._persist_state()
        self._tree_snaps[tree] = self.channel.snapshot()
        self.hr = self._staged.activate(tree)
        self.staged_activations += 1

    # -- serving --------------------------------------------------------
    def _serve_setup(self, payload) -> None:
        from ..kernels.common import default_interpret
        from ..serving.engine import PartyBits
        from ..serving.export import export_host, load_host
        from ..serving.packed import host_half_from_keys
        if self._current_tree is not None:
            # training is over: the in-flight tree's table is final —
            # make it durable before serving depends on it
            self._complete_tree(self._current_tree)
            self._current_tree = None
            self._persist_state()
        keys = [(int(ti), int(nid)) for ti, nid in payload["keys"]]
        half = host_half_from_keys(self.hid, keys, self.tables,
                                   self.data.thresholds, self.params.n_bins)
        # the guest names the export root in the setup frame so one
        # serve() call produces ONE coherent per-party tree; the
        # constructor's export_dir is only the fallback
        export_dir = payload.get("export_dir", self.export_dir)
        if export_dir:
            out = export_host(half, os.path.join(export_dir,
                                                 f"host{self.hid}"))
            half = load_host(out)   # serve from the RELOADED export
        use_pallas = self.params.use_pallas and not default_interpret()
        self._serve_k = half.table.k
        self.server = (PartyBits(half.table, half.thresholds, half.n_bins,
                                 use_pallas)
                       if half.table.k else None)
        self.channel.control_send("guest", wire.SERVE_READY,
                                  {"k": self._serve_k})

    def _predict(self, req) -> None:
        ids = np.asarray(req["ids"])
        n = len(ids)
        n_pad = int(req["n_pad"])
        if n and int(ids.max()) >= len(self.X_serve):
            # application-level rejection (RemoteError): the party is
            # alive and answering, so serving must NOT type this as
            # PartyUnavailable or burn reconnect retries on it
            raise RemoteError(
                f"host{self.hid}: predict_req references row "
                f"{int(ids.max())} but only {len(self.X_serve)} rows are "
                f"staged — ship this batch's host rows first "
                f"(MultiHostRun.stage_host_data / the serve_data frame)")
        pb = self.server.packed_from_X(self.X_serve[ids], n_pad)
        # round-trips are counted ONCE, at the guest's collect site (the
        # same place the in-process engine counts them) — not here, or
        # merged_stats would double-count every batch
        self.channel.send(f"host{self.hid}", "guest", wire.PREDICT_BITS, pb,
                          self._serve_k * ((n + 7) // 8))

    # -- introspection --------------------------------------------------
    def status(self) -> dict:
        """Live snapshot of this party: Stats, training metrics,
        transport metrics, ledger, trace occupancy, protocol position.
        The ``status`` control frame returns exactly this dict."""
        return {"hid": self.hid,
                "stats": self.stats.as_dict(),
                "metrics": self.stats.metrics.snapshot(),
                "transport": self.channel.metrics.snapshot(),
                "ledger": self.channel.summary(),
                "socket": self.channel.socket_summary(),
                "trace": {"enabled": bool(self.tracer.enabled),
                          "events": len(self.tracer),
                          "dropped": int(self.tracer.dropped)},
                "current_tree": (int(self._current_tree)
                                 if self._current_tree is not None
                                 else None),
                "n_complete": len(self._complete)}

    # -- control --------------------------------------------------------
    def _control(self, tag: str, payload) -> bool:
        if tag == wire.SERVE_SETUP:
            self._serve_setup(payload)
        elif tag == wire.SERVE_DATA:
            # out-of-band data staging: in a real deployment each party
            # pulls the batch's rows from its OWN source; the control
            # plane simulates that arrival.  predict_req still carries
            # only instance ids.
            self.X_serve = np.asarray(payload["X"])
        elif tag == wire.RESET_STATS:
            # a refit starts: fresh Stats (the next enc_gh's engine binds
            # to it) and fresh per-fit wire accounting, mirroring the
            # fresh model the guest constructs
            self.stats = Stats()
            self.channel.reset_accounting()
            self.tracer.clear()     # per-fit, like the ledger
        elif tag == wire.GET_STATS:
            self.channel.control_send(
                "guest", wire.STATS,
                {"stats": self.stats.as_dict(),
                 "ledger": self.channel.summary(),
                 "socket": self.channel.socket_summary()})
        elif tag == wire.STATUS:
            self.channel.control_send("guest", wire.STATUS_REPLY,
                                      self.status())
        elif tag == wire.TRACE_SYNC:
            # ship this party's trace ring to the guest, stamped with our
            # perf_counter_ns clock: the guest's send/recv times around
            # this round-trip give one NTP-style offset sample (min-RTT
            # across these + heartbeat samples wins, obs/export.py)
            self.channel.control_send(
                "guest", wire.TRACE_DUMP,
                {"hid": self.hid,
                 "clock": time.perf_counter_ns(),
                 "events": self.tracer.export_events(),
                 "dropped": int(self.tracer.dropped)})
            if isinstance(payload, dict) and payload.get("clear"):
                self.tracer.clear()
        elif tag == wire.PING:
            self.channel.control_send("guest", wire.PONG, payload)
        elif tag == wire.HB:
            # liveness probe from the guest's supervisor thread: the ack
            # is skimmed by the guest's recv loop, never blocking the
            # protocol (a wedged host simply never reaches this branch).
            # Echo the payload and add our monotonic clock — each ack is
            # a free clock-offset sample for trace merging.
            ack = dict(payload) if isinstance(payload, dict) else {}
            ack["clock"] = time.perf_counter_ns()
            self.channel.control_send("guest", wire.HB_ACK, ack)
        elif tag == wire.RESYNC:
            # reconnect barrier: by the time this frame is processed,
            # every reply this host owed for earlier frames has already
            # been written to the stream (frames are handled in order) —
            # the guest drains until this ack and the stream is clean
            self.channel.control_send("guest", wire.RESYNC_ACK, payload)
        elif tag == wire.BYE:
            return False
        else:
            raise TransportError(f"host{self.hid}: unknown control tag "
                                 f"{tag!r}")
        return True


# Spawned host parties run JAX on this platform.  The guest process holds
# the accelerator (a chip belongs to one process at a time), so a child
# left to pick its own backend would race the parent for it.  The platform
# reaches the child through its environment, which it reads before it
# imports JAX.
HOST_PLATFORM = "cpu"
_SPAWN_ENV_LOCK = threading.Lock()


def _start_with_platform(proc, platform: str) -> None:
    """Start a spawn-context process with ``JAX_PLATFORMS=platform`` in its
    environment (the spawned interpreter copies ``os.environ`` at start);
    the parent's own environment is restored before this returns."""
    with _SPAWN_ENV_LOCK:
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = platform
        try:
            proc.start()
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev


def _wrap_fault(ep, fault_plan):
    if fault_plan is None:
        return ep
    from .chaos import FaultyEndpoint
    return FaultyEndpoint(ep, fault_plan)


def host_main(port: int, hid: int, params, X_host,
              export_dir: str | None = None,
              state_dir: str | None = None, run_id: str = "",
              fault_plan=None, timeout: float = 600.0,
              max_redials: int = 8, redial_backoff: float = 0.1) -> None:
    """Entry point of a spawned host process: connect to the guest's
    listener, perform the session handshake (run id, party id, resume
    floor), serve frames until ``bye``.  On connection loss the process
    RE-DIALS with exponential backoff + jitter and carries on — its
    in-memory state (tables, ledger, seq counters) survives, and the
    guest's tree replay brings the protocol back in step.  Only a process
    death loses memory state, which is what ``state_dir`` is for."""
    import jax
    print(f"host{hid}: JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')} "
          f"backend={jax.default_backend()}", flush=True)
    jitter = _random.Random((hid + 1) * 7919)
    pp = None
    channel = None
    redials = 0
    fault_plan = fault_plan.fresh() if fault_plan is not None else None
    while True:
        try:
            sock = _socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
            ep = _wrap_fault(SocketEndpoint(sock), fault_plan)
        except OSError as e:
            redials += 1
            if redials > max_redials:
                raise TransportError(
                    f"host{hid}: guest unreachable after "
                    f"{max_redials} dials: {e}") from e
            time.sleep(redial_backoff * (2 ** (redials - 1))
                       + jitter.uniform(0, redial_backoff))
            continue
        if pp is None:
            channel = TransportChannel(f"host{hid}", {"guest": ep},
                                       timeout)
            pp = PartyProcess(hid, params, X_host, channel,
                              export_dir=export_dir, state_dir=state_dir,
                              own_process=True)
        else:
            channel.peers["guest"] = ep
        if getattr(params, "pipeline", False):
            # async inbox (DESIGN.md §12): accept the pipelined guest's
            # next-round enc_gh off the wire while this round computes.
            # Restarted per connection — a re-dial leaves the previous
            # broker poisoned on the dead endpoint.
            channel.start_broker("guest")
        channel.control_send(
            "guest", wire.HELLO,
            {"hid": hid, "run_id": run_id, "resume": pp.resume_info()})
        try:
            pp.serve_forever()
            ep.close()
            return
        except TransportError:
            # connection-level failure (drop, mid-frame timeout, corrupt
            # frame): close, back off, re-dial, resume.  Anything else is
            # a real host-side crash and must kill the process — the
            # guest respawns it from durable state.
            ep.close()
            redials += 1
            if redials > max_redials:
                raise
            time.sleep(redial_backoff * (2 ** (redials - 1))
                       + jitter.uniform(0, redial_backoff))


# ---------------------------------------------------------------------------
# guest-side orchestration
# ---------------------------------------------------------------------------

class MultiHostRun:
    """Drive a process-per-party run from the guest side.

    ``transport="socket"`` spawns one OS process per host (multiprocessing
    ``spawn`` — a fresh interpreter, so jax state is never forked) talking
    length-prefixed TCP on localhost; each child runs JAX on
    ``HOST_PLATFORM``, never on the accelerator this process may hold.
    ``transport="loopback"`` builds the host PartyProcess objects in this
    process on in-memory endpoints with the identical framing — same codec,
    same ledgers, no sockets — which is what CI uses where spawning is too
    slow.

        run = MultiHostRun(params, [X_host])
        model = run.fit(X_guest, y)         # training over the transport
        run.serve(out_dir)                  # per-party exports, reloaded
        score = run.predict_score(X_eval_guest)
        run.close()
    """

    def __init__(self, params, X_hosts: list, transport: str = "socket",
                 export_dir: str | None = None, timeout: float = 600.0,
                 state_dir: str | None = None, fault_plans: dict | None = None,
                 liveness_interval: float | None = None,
                 liveness_timeout: float = 10.0,
                 serve_timeout: float | None = None):
        if getattr(params, "mesh", None) is not None:
            raise ValueError("multi-host runtime: params.mesh must be None "
                             "(per-process meshes are per-party state)")
        self.params = params
        self.n_hosts = len(X_hosts)
        self.export_dir = export_dir
        self.state_dir = state_dir
        self.fault_plans = fault_plans or {}
        self.transport = transport
        self.timeout = timeout
        self.liveness_interval = liveness_interval
        self.liveness_timeout = liveness_timeout
        self.serve_timeout = serve_timeout
        self.procs: list = []
        self.parties: list = []         # loopback PartyProcess objects
        self._listener = None
        self._port = None
        self.model = None
        self.predictor = None
        self.run_id = f"run-{os.getpid()}-{os.urandom(4).hex()}"
        self.restarts = 0               # host processes respawned
        self.redials = 0                # connections re-accepted (host
                                        # process survived, socket didn't)
        self.wedged_restarts = 0        # supervisor-initiated restarts
        self.slow_hosts: set = set()    # straggling, NOT restarted
        self._degraded: set = set()     # serving: hosts awaiting rejoin
        self._host_resume: dict = {}    # hid -> last hello resume info
        self._host_keys = None          # serve_setup keys (for re-setup)
        self._round_snaps: dict = {}    # round -> guest channel snapshot
        self._mp_ctx = None
        from ..core.binning import BinnedData
        from ..data.pipeline import RowBlocks
        # pre-binned / chunked host sources pass through untouched (§13);
        # note RowBlocks carries a closure, so socket spawn requires raw
        # arrays or a (picklable, device-buffer-free) BinnedData
        self._X_hosts = [X if isinstance(X, (BinnedData, RowBlocks))
                         else np.asarray(X) for X in X_hosts]
        self._supervisor = None
        self._straggler = {}
        self._clock_samples = {}    # hid -> [(t_send, peer_clock, t_recv)]
                                    # in guest perf_counter_ns (trace merge)

        self.channel = TransportChannel("guest", {}, timeout)
        if transport == "socket":
            import multiprocessing as mp
            self._mp_ctx = mp.get_context("spawn")
            self._listener = _socket.socket()
            try:
                self._listener.bind(("127.0.0.1", 0))
                self._listener.listen(self.n_hosts + 2)
                self._port = self._listener.getsockname()[1]
                for hid in range(self.n_hosts):
                    self.procs.append(self._spawn(hid, first=True))
                self._accept_hosts(set(range(self.n_hosts)), timeout)
            except BaseException:
                # __init__ failed: the caller never gets an object to
                # close(), so reap children and sockets here
                for ep in self.channel.peers.values():
                    ep.close()
                for p in self.procs:
                    if p.is_alive():
                        p.terminate()
                self._listener.close()
                raise
        elif transport == "loopback":
            for hid, X in enumerate(self._X_hosts):
                guest_end, host_end = LoopbackEndpoint.pair()
                hch = TransportChannel(f"host{hid}", {"guest": host_end},
                                       timeout)
                pp = PartyProcess(hid, params, X, hch,
                                  export_dir=export_dir,
                                  state_dir=state_dir)
                host_end.on_deliver = pp.pump
                self.channel.peers[f"host{hid}"] = guest_end
                self.parties.append(pp)
        else:
            raise ValueError(f"unknown transport {transport!r}")

    # -- spawn / accept / reacquire -------------------------------------
    def _spawn(self, hid: int, first: bool = False):
        """Start (or restart) host ``hid``.  Fault plans are injected
        only into the FIRST generation: a respawned process runs clean,
        or a deterministic kill-at-(tree, layer) rule would re-fire on
        every replay and the run could never converge."""
        plan = self.fault_plans.get(hid) if first else None
        p = self._mp_ctx.Process(
            target=host_main,
            args=(self._port, hid, self.params, self._X_hosts[hid],
                  self.export_dir, self.state_dir, self.run_id, plan,
                  self.timeout),
            daemon=True)
        _start_with_platform(p, HOST_PLATFORM)
        return p

    def _accept_hosts(self, want: set, deadline_s: float) -> None:
        """Accept re-/connections until every hid in ``want`` has a live
        endpoint.  Any host may dial in (a re-dialing survivor arrives
        interleaved with the respawn we are waiting for) — each hello is
        routed to its own hid slot and the freshest connection wins."""
        deadline = time.monotonic() + deadline_s
        while want:
            budget = deadline - time.monotonic()
            if budget <= 0:
                dead = [p.pid for p in self.procs if not p.is_alive()]
                raise TransportError(
                    f"host(s) {sorted(want)} never (re)connected within "
                    f"{deadline_s}s (dead processes: {dead or 'none'})")
            self._listener.settimeout(min(budget, 1.0))
            try:
                sock, _ = self._listener.accept()
            except _socket.timeout:
                for hid in sorted(want):    # crashed before connecting?
                    if hid < len(self.procs) \
                            and not self.procs[hid].is_alive():
                        self.procs[hid] = self._spawn(hid)
                        self.restarts += 1
                continue
            ep = SocketEndpoint(sock)
            try:
                frame = ep.recv_bytes(min(max(budget, 1.0), 10.0))
                _, _, _, tag, _, _, hello = decode_frame(frame)
            except TransportError:
                ep.close()
                continue
            if tag != wire.HELLO or hello.get("run_id") != self.run_id:
                ep.close()          # stale dialer from a previous run
                continue
            hid = int(hello["hid"])
            old = self.channel.peers.get(f"host{hid}")
            if old is not None:
                old.close()
            self.channel.peers[f"host{hid}"] = ep
            with self.channel._mirror_lock:
                self.channel.rx_bytes[wire.HELLO] += len(frame) + 4
            self._host_resume[hid] = hello.get("resume") or {}
            want.discard(hid)

    def _reacquire(self, peer: str) -> None:
        """Reconnect hook: a send/recv to ``peer`` failed.  Respawn the
        process if it died, re-accept its dial-in, then raise
        :class:`PeerRestarted` — the peer's in-flight tree state is gone
        (or unsynchronized), so the resilient loop must replay from the
        last boundary rather than retry the failed frame."""
        if self.transport != "socket" or not peer.startswith("host"):
            return
        hid = int(peer[4:])
        respawned = False
        if not self.procs[hid].is_alive():
            self.procs[hid].join(timeout=1)
            self.procs[hid] = self._spawn(hid)
            self.restarts += 1
            respawned = True
        else:
            self.redials += 1
        self._accept_hosts({hid}, self.timeout)
        raise PeerRestarted(
            f"{peer} {'respawned' if respawned else 'reconnected'}: "
            f"replay from the last tree boundary")

    def _recover_and_resync(self) -> None:
        """Bring every peer to a known-clean stream state before a
        replay: respawn/reaccept anything broken, then run the resync
        barrier against every host — stale in-flight replies from the
        aborted attempt are drained unmirrored (the rolled-back snapshot
        already forgot their requests)."""
        t_ns = (time.perf_counter_ns() if self.channel.tracer.enabled
                else 0)
        hook, self.channel.reconnect = self.channel.reconnect, None
        try:
            if self.transport == "socket":
                broken = {hid for hid in range(self.n_hosts)
                          if not self.procs[hid].is_alive()
                          or getattr(self.channel.peers.get(f"host{hid}"),
                                     "dead", False)}
                for hid in sorted(broken):
                    if not self.procs[hid].is_alive():
                        self.procs[hid].join(timeout=1)
                        self.procs[hid] = self._spawn(hid)
                        self.restarts += 1
                if broken:
                    self._accept_hosts(broken, self.timeout)
            for hid in range(self.n_hosts):
                for attempt in (0, 1):
                    try:
                        self.channel.control_send(f"host{hid}", wire.RESYNC,
                                                  {"run": self.run_id})
                        self.channel.drain(f"host{hid}",
                                           until_ctrl=wire.RESYNC_ACK,
                                           timeout=self.timeout)
                        break
                    except TransportError:
                        if attempt or self.transport != "socket":
                            raise
                        # connection died between the hook firing and
                        # now: one more respawn/accept round, then give
                        # up to the outer retry budget
                        if not self.procs[hid].is_alive():
                            self.procs[hid].join(timeout=1)
                            self.procs[hid] = self._spawn(hid)
                            self.restarts += 1
                        self._accept_hosts({hid}, self.timeout)
        finally:
            self.channel.reconnect = hook
            if self.channel.tracer.enabled:
                self.channel.tracer.complete(
                    "resync", t_ns, time.perf_counter_ns() - t_ns,
                    cat="transport", n_hosts=self.n_hosts)

    def _resume_floor(self) -> int | None:
        """Lowest boosting round any reconnected party can resume from,
        in ROUND units (None: nobody reported resume info)."""
        if not self._host_resume or self.model is None:
            return None
        tpr = self.model.trees_per_round
        floors = [int(r.get("n_complete", 0)) // tpr
                  for r in self._host_resume.values() if r is not None]
        return min(floors) if floors else None

    # -- training -------------------------------------------------------
    def fit(self, X_guest, y, *, resilient: bool = False,
            ckpt_dir: str | None = None, save_every: int = 1,
            max_retries: int = 3, retry_backoff: float = 0.05):
        from ..core.boosting import VerticalBoosting
        if resilient and getattr(self.params, "pipeline", False):
            # the resilient loop's replay anchor is the enc_gh boundary
            # of ONE in-flight tree; pipelining keeps a second tree's
            # enc_gh in flight past that boundary, so a rollback could
            # not decide which staged state to discard.  Train pipelined
            # OR resilient, not both.
            raise ValueError("pipeline=True is incompatible with "
                             "resilient=True: the replay boundary admits "
                             "a single in-flight tree")
        # per-fit accounting on BOTH sides of the wire: the model's Stats
        # are fresh, so the channel ledgers and host Stats must be too,
        # or a refit on a long-lived run double-counts
        self.channel.serving_mode = False
        self.channel.reset_accounting()
        for hid in range(self.n_hosts):
            self.channel.control_send(f"host{hid}", wire.RESET_STATS, None)
        model = VerticalBoosting(self.params)
        model.channel = self.channel
        model.remote_hosts = [RemoteHostHandle(self.channel, hid)
                              for hid in range(self.n_hosts)]
        self.model = model
        self.predictor = None           # stale after refit
        if not resilient:
            model.fit(X_guest, y, [])
            return model
        if ckpt_dir is None:
            raise ValueError("resilient fit needs ckpt_dir: the per-round "
                             "score is restored through the checkpoint "
                             "machinery on replay")
        self._fit_resilient(model, X_guest, y, ckpt_dir, save_every,
                            max_retries, retry_backoff)
        return model

    def _fit_resilient(self, model, X_guest, y, ckpt_dir: str,
                       save_every: int, max_retries: int,
                       retry_backoff: float) -> None:
        """The per-tree resume boundary: each boosting round runs inside
        a :class:`~repro.runtime.fault.ResilientLoop` step.  On any
        failure the loop restores the last round boundary — score from
        the checkpoint, trees truncated in memory, ledger/seq state from
        the round snapshot — re-syncs every peer, and replays.  The
        replayed round is bit-identical (GOSS/shuffle streams are keyed
        by absolute tree index; the affine/Paillier pipelines decrypt
        identically) and the converged ledgers match the fault-free
        oracle (duplicates deduped by seq, aborted attempts rolled back)."""
        from ..checkpoint import checkpoint as _ckpt
        from .fault import ResilientLoop
        score0 = model.begin_fit(X_guest, y, [])
        shape, dtype = score0.shape, score0.dtype
        self._round_snaps = {0: self.channel.snapshot()}
        self._host_resume = {}
        self._start_supervisor()
        try:
            self.channel.reconnect = self._reacquire
            self.channel.on_rtt = self._observe_rtt

            def step_fn(score, t):
                self._round_snaps[t] = self.channel.snapshot()
                return model.boost_round(t, score)

            def save_fn(step, score):
                _ckpt.save(ckpt_dir, step, {"score": np.asarray(score)})

            def restore_fn():
                self._recover_and_resync()
                avail = _ckpt.latest_step(ckpt_dir)
                step = avail if avail is not None else 0
                floor = self._resume_floor()
                if floor is not None:
                    step = min(step, floor)
                self._host_resume = {}
                if avail is not None and step > 0:
                    # restore_any, not restore: the jax path would
                    # canonicalize the float64 score to float32 and the
                    # replayed rounds would drift off bit-identity
                    score = np.asarray(
                        _ckpt.restore_any(ckpt_dir, step)["score"])
                    assert score.shape == shape and score.dtype == dtype
                else:
                    step, score = 0, score0.copy()
                model.rollback_to_round(step)
                self.channel.restore(self._round_snaps[step])
                return step, score

            loop = ResilientLoop(step_fn, save_fn, restore_fn,
                                 next_batch=lambda t: t,
                                 save_every=save_every,
                                 max_retries=max_retries,
                                 backoff=retry_backoff)
            self.failures = 0
            _, score = loop.run(score0, 0, self.params.n_trees)
            self.failures = loop.failures
            model.finish_fit(score)
        finally:
            self.channel.reconnect = None
            self.channel.on_rtt = None
            self._stop_supervisor()

    def _observe_rtt(self, src: str, tag: str, seconds: float) -> None:
        """Per-layer round-trip times feed the straggler policy: a SLOW
        host is marked (``slow_hosts``) but never restarted — restarting
        it would lose real progress for no correctness gain.  Only the
        liveness supervisor (no hb_ack at all) restarts a host."""
        if tag != wire.SPLIT_INFOS:
            return
        from .fault import StragglerPolicy
        pol = self._straggler.get(src)
        if pol is None:
            pol = self._straggler[src] = StragglerPolicy()
        if pol.check(seconds):
            self.slow_hosts.add(src)

    # -- liveness supervisor --------------------------------------------
    def _start_supervisor(self) -> None:
        if self.liveness_interval is None or self.transport != "socket":
            return
        self._last_ack = {hid: time.monotonic()
                          for hid in range(self.n_hosts)}
        self.channel.on_ctrl = self._skim_ctrl
        self._sup_stop = threading.Event()
        self._supervisor = threading.Thread(target=self._supervise,
                                            daemon=True)
        self._supervisor.start()

    def _stop_supervisor(self) -> None:
        if self._supervisor is not None:
            self._sup_stop.set()
            self._supervisor.join(timeout=5)
            self._supervisor = None
        self.channel.on_ctrl = None

    def _skim_ctrl(self, src: str, tag: str, payload) -> bool:
        """Recv-loop hook: heartbeat acks arrive interleaved with
        protocol replies (the supervisor pings while the training thread
        owns the socket reads) — record and swallow them."""
        if tag == wire.HB_ACK:
            try:
                hid = int(src[4:])
                self._last_ack[hid] = time.monotonic()
                if isinstance(payload, dict) and "clock" in payload \
                        and "t_ns" in payload:
                    # one NTP-style offset sample per ack (min-RTT sample
                    # wins at merge time); bounded — samples only improve
                    # while RTT keeps making new minimums anyway
                    samples = self._clock_samples.setdefault(hid, [])
                    if len(samples) < 256:
                        samples.append((int(payload["t_ns"]),
                                        int(payload["clock"]),
                                        time.perf_counter_ns()))
            except (ValueError, AttributeError):
                pass
            return True
        return False

    def _supervise(self) -> None:
        """Wedged-vs-slow triage.  A SLOW host still answers heartbeats
        (and shows up in ``slow_hosts`` via the straggler policy on
        per-layer RTTs): left alone.  A WEDGED host answers nothing for
        ``liveness_timeout``: kill it — the training thread's blocked
        recv fails over the closed socket, the reconnect hook respawns
        from durable state, and the resilient loop replays the tree."""
        while not self._sup_stop.wait(self.liveness_interval):
            now = time.monotonic()
            for hid in range(self.n_hosts):
                try:
                    self.channel.control_send(
                        f"host{hid}", wire.HB,
                        {"t": now, "t_ns": time.perf_counter_ns()})
                except Exception:                        # noqa: BLE001
                    continue        # training thread handles reconnects
                if now - self._last_ack[hid] > self.liveness_timeout:
                    p = self.procs[hid]
                    if p.is_alive():
                        p.kill()
                    ep = self.channel.peers.get(f"host{hid}")
                    if ep is not None:
                        ep.close()
                    self.wedged_restarts += 1
                    self._last_ack[hid] = now   # one kill per silence

    # -- serving --------------------------------------------------------
    def serve(self, out_dir: str | None = None):
        """Export per-party halves (guest here, each host in its own
        process), reload them, and wire a predictor over the transport.
        Returns the :class:`FederatedPredictor`."""
        from ..serving.engine import FederatedPredictor
        from ..serving.export import export_guest, load_guest
        from ..serving.packed import pack_guest
        if self.model is None:
            raise RuntimeError("serve() needs a fitted model: call fit()")
        out_dir = out_dir or self.export_dir
        guest_half, host_keys = pack_guest(self.model)
        self._host_keys = host_keys
        self._serve_out_dir = out_dir
        if out_dir:
            gdir = export_guest(guest_half,
                                os.path.join(out_dir, "guest"))
            guest_half = load_guest(gdir)   # serve from the reloaded half
        for hid in range(self.n_hosts):
            self._serve_setup_host(hid)
        remote = []
        for hid in range(self.n_hosts):
            ack = self.channel.control_recv(f"host{hid}", wire.SERVE_READY)
            remote.append(RemoteServingHost(self.channel, hid,
                                            int(ack["k"]),
                                            self.serve_timeout))
        self.predictor = FederatedPredictor(
            guest_half, remote, channel=self.channel,
            stats=self.model.stats)
        # from here on a transport failure on a host is a per-batch,
        # typed PartyUnavailable — never a hang, never partial bits
        self.channel.serving_mode = True
        return self.predictor

    def _serve_setup_host(self, hid: int) -> None:
        self.channel.control_send(
            f"host{hid}", wire.SERVE_SETUP,
            {"keys": [list(k) for k in self._host_keys[hid]],
             "export_dir": self._serve_out_dir})

    def _heal_serving(self) -> None:
        """Rejoin degraded hosts before the next batch: accept the
        re-dial (respawning first if the process died), replay the
        serving setup, and clear the mark.  If a host is still down the
        typed error surfaces again — per batch, never a hang."""
        from ..core.party import PartyUnavailable
        for hid in sorted(self._degraded):
            peer = f"host{hid}"
            try:
                if not self.procs[hid].is_alive():
                    self.procs[hid].join(timeout=1)
                    self.procs[hid] = self._spawn(hid)
                    self.restarts += 1
                self._accept_hosts({hid}, self.timeout)
                self._align_seqs(hid)
                self._serve_setup_host(hid)
                ack = self.channel.control_recv(peer, wire.SERVE_READY)
            except PartyUnavailable:
                raise
            except (TransportError, OSError) as e:
                raise PartyUnavailable(peer, f"rejoin failed: {e}") from e
            if int(ack["k"]) != self.predictor.hosts[hid].k:
                raise PartyUnavailable(
                    peer, f"rejoined with {int(ack['k'])} serving nodes, "
                          f"expected {self.predictor.hosts[hid].k}")
            self._degraded.discard(hid)

    def _align_seqs(self, hid: int) -> None:
        """Converge per-tag seq counters with a rejoined party.  Its
        stream state restarts from the persisted floor (which has no
        serving tags at all), while the guest's counters are wherever
        the dead generation left them — without alignment the fresh
        host's first ``predict_bits`` (seq 1) looks like a replayed
        duplicate and is silently discarded, wedging the batch."""
        peer = f"host{hid}"
        resume = self._host_resume.get(hid) or {}
        for key in [k for k in self.channel.send_seq if k[0] == peer]:
            del self.channel.send_seq[key]
        for key in [k for k in self.channel.last_seen if k[0] == peer]:
            del self.channel.last_seen[key]
        # our next send must be numbered one past what the host has seen
        for st, v in (resume.get("last_seen") or {}).items():
            src, tag = st.split("|", 1)
            if src == self.channel.party:
                self.channel.send_seq[(peer, tag)] = int(v)
        # and its next send will be numbered one past what it has sent
        for dt, v in (resume.get("send_seq") or {}).items():
            dst, tag = dt.split("|", 1)
            if dst == self.channel.party:
                self.channel.last_seen[(peer, tag)] = int(v)

    def stage_host_data(self, X_hosts: list) -> None:
        """Ship each host its OWN feature rows for the upcoming batch —
        the out-of-band data arrival every party sees in a real
        deployment (the serving protocol itself still moves only
        instance ids and bit blocks)."""
        for hid, X in enumerate(X_hosts):
            self.channel.control_send(f"host{hid}", wire.SERVE_DATA,
                                      {"X": np.asarray(X)})

    def predict_score(self, X_guest, X_hosts: list | None = None, *,
                      staged: bool = False) -> np.ndarray:
        """Serve one batch.  Pass ``X_hosts`` to stage each host's rows
        for THIS batch, or ``staged=True`` to assert the hosts already
        hold the right rows (initially their training matrices).  With
        neither, raise: a guest batch silently scored against stale host
        rows mixes features from different instances with no error."""
        from ..core.party import PartyUnavailable
        if self.predictor is None:
            self.serve()
        if self._degraded:
            self._heal_serving()        # raises PartyUnavailable if a
                                        # marked host has not rejoined
        try:
            if X_hosts is not None:
                self.stage_host_data(X_hosts)
            elif not staged:
                raise ValueError(
                    "host rows for this batch are not staged: pass X_hosts "
                    "(ships each host its rows) or staged=True (the hosts' "
                    "currently staged matrices ARE this batch's rows)")
            return self.predictor.predict_score(X_guest,
                                                [None] * self.n_hosts)
        except PartyUnavailable as e:
            # this batch is lost (typed, whole-batch — the engine already
            # consumed every healthy host's reply, so the streams stay
            # clean); the NEXT batch triggers the rejoin path above
            self._degraded.add(int(e.party[4:]))
            raise

    # -- diagnostics ----------------------------------------------------
    def host_stats(self) -> list:
        """Each host's Stats/ledger/socket counters (control round-trip)."""
        out = []
        for hid in range(self.n_hosts):
            self.channel.control_send(f"host{hid}", wire.GET_STATS, None)
            out.append(self.channel.control_recv(f"host{hid}", wire.STATS))
        return out

    def merged_stats(self) -> Stats:
        """Guest stats + every host's counters folded in: comparable to
        the single shared Stats of an in-process run."""
        merged = Stats()
        merged.merge_counts(self.model.stats.as_dict())
        for hs in self.host_stats():
            merged.merge_counts(hs["stats"])
        return merged

    def party_status(self, hid: int = 0) -> dict:
        """Live introspection of one host party over the control plane:
        Stats, training + transport metric snapshots, ledger, trace
        occupancy, protocol position (``PartyProcess.status``)."""
        self.channel.control_send(f"host{hid}", wire.STATUS, None)
        return self.channel.control_recv(f"host{hid}", wire.STATUS_REPLY)

    def collect_traces(self, clear: bool = False) -> list:
        """One ``trace_sync`` round-trip per host.  Returns
        ``[{hid, events, dropped, samples}]`` where ``samples`` are
        ``(t_send, peer_clock, t_recv)`` clock-offset observations on
        the guest clock — the sync round-trip itself always contributes
        one; supervisor heartbeat acks (when liveness is on) add more."""
        out = []
        for hid in range(self.n_hosts):
            t0 = time.perf_counter_ns()
            self.channel.control_send(f"host{hid}", wire.TRACE_SYNC,
                                      {"clear": bool(clear)})
            dump = self.channel.control_recv(f"host{hid}", wire.TRACE_DUMP)
            t1 = time.perf_counter_ns()
            samples = list(self._clock_samples.get(hid, ()))
            samples.append((t0, int(dump["clock"]), t1))
            out.append({"hid": hid, "events": dump["events"],
                        "dropped": int(dump["dropped"]),
                        "samples": samples})
        return out

    def trace(self, path: str | None = None) -> list:
        """Merge the guest's trace with every host's (clock-aligned onto
        the guest timeline) and optionally write Perfetto ``trace.json``
        at ``path``.  Returns the merged, time-sorted event list."""
        from ..obs.export import (estimate_offset, merge_traces,
                                  write_perfetto)
        parties = []
        gt = getattr(self.model, "tracer", None) if self.model else None
        if gt is not None and gt.enabled:
            parties.append({"party": "guest", "pid": 0,
                            "events": gt.export_events(), "offset_ns": 0})
        for dump in self.collect_traces():
            off, _ = estimate_offset(dump["samples"])
            parties.append({"party": f"host{dump['hid']}",
                            "pid": dump["hid"] + 1,
                            "events": dump["events"],
                            "offset_ns": off})
        merged = merge_traces(parties)
        if path:
            write_perfetto(path, merged, parties)
        return merged

    def ping(self, hid: int = 0) -> float:
        """One control round-trip, seconds."""
        t0 = time.perf_counter()
        self.channel.control_send(f"host{hid}", wire.PING, {"t": t0})
        self.channel.control_recv(f"host{hid}", wire.PONG)
        return time.perf_counter() - t0

    def close(self, join_timeout: float = 30.0) -> None:
        self._stop_supervisor()
        self.channel.serving_mode = False   # byes must not come back as
                                            # typed PartyUnavailable
        for hid in range(self.n_hosts):
            try:
                self.channel.control_send(f"host{hid}", wire.BYE, None)
            except (TransportError, OSError):
                pass        # peer already dead (crashed host, reset pipe)
        # join -> terminate -> join -> kill: a host wedged in a blocking
        # recv (or one that traps SIGTERM) must not outlive the run —
        # SIGKILL is the floor of the escalation, and the final join
        # reaps the zombie so the process table stays clean
        for p in self.procs:
            p.join(timeout=join_timeout)
        for p in self.procs:
            if p.is_alive():
                p.terminate()
        for p in self.procs:
            if p.is_alive():
                p.join(timeout=5)
                if p.is_alive():
                    p.kill()
                    p.join(timeout=5)
        self.channel.close()
        if self._listener is not None:
            self._listener.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
