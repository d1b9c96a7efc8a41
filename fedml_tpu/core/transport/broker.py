"""TCP pub/sub broker: the `TopicBus` interface served over a socket.

The reference's MQTT backends talk to an EXTERNAL broker process via
paho-mqtt (``fedml_core/distributed/communication/mqtt/
mqtt_comm_manager.py:14,47-57``): the broker is what makes the pub/sub
path cross-process. No MQTT broker exists in this environment, so this
module provides the minimal broker a federated run needs:

- :class:`BrokerDaemon` — a standalone TCP daemon (also runnable as
  ``python -m fedml_tpu.core.transport.broker --port N``) that routes
  PUBLISH frames to every connection SUBSCRIBEd to the topic. Like an
  MQTT broker, it is payload-agnostic: the federated wire codec rides
  through it untouched.
- :class:`RemoteTopicBus` — the client side; implements the same
  ``subscribe(topic, cb)`` / ``publish(topic, payload)`` contract as the
  in-process :class:`~fedml_tpu.core.transport.pubsub.TopicBus`, so
  ``PubSubTransport`` / ``PubSubBlobTransport`` run unchanged across OS
  processes (paho analog: ``mqtt.Client`` + network-loop thread calling
  ``on_message``).

Wire protocol (both directions, length-prefixed frames)::

    op(1: b"S" subscribe | b"P" publish) || u32 topic_len || topic utf-8
        || u64 payload_len || payload

Subscribe frames carry an empty payload. Delivery semantics match MQTT
QoS 0: no retained messages, publishes to a topic with no subscriber are
dropped (deployment readiness must therefore be handshaken above the
transport — see :mod:`fedml_tpu.experiments.deploy`).
"""

from __future__ import annotations

import argparse
import os
import queue
import socket
import struct
import threading
from typing import Callable

from fedml_tpu.core import telemetry
from fedml_tpu.core.transport.retry import (
    RetryExhausted,
    RetryPolicy,
    iter_attempts,
)

_OP_SUB = b"S"
_OP_PUB = b"P"
_TOPIC_HDR = struct.Struct(">I")
_PAYLOAD_HDR = struct.Struct(">Q")

#: Outbound frames queued per subscriber before the broker declares it
#: wedged and drops it (MQTT brokers do the same with their inflight
#: window; QoS-0 semantics make the drop legal).
_SUB_QUEUE_MAX = 256
#: Socket-level send timeout per frame to one subscriber.
_SUB_SEND_TIMEOUT_S = 10.0


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except OSError:
            return None
        if not chunk:
            return None
        buf += chunk
    return buf


def _read_frame(sock: socket.socket) -> tuple[bytes, str, bytes] | None:
    op = _recv_exact(sock, 1)
    if op is None:
        return None
    hdr = _recv_exact(sock, _TOPIC_HDR.size)
    if hdr is None:
        return None
    (tlen,) = _TOPIC_HDR.unpack(hdr)
    topic = _recv_exact(sock, tlen)
    if topic is None:
        return None
    hdr = _recv_exact(sock, _PAYLOAD_HDR.size)
    if hdr is None:
        return None
    (plen,) = _PAYLOAD_HDR.unpack(hdr)
    payload = _recv_exact(sock, plen) if plen else b""
    if payload is None:
        return None
    return op, topic.decode("utf-8"), payload


def _frame(op: bytes, topic: str, payload: bytes = b"") -> bytes:
    t = topic.encode("utf-8")
    return (
        op + _TOPIC_HDR.pack(len(t)) + t
        + _PAYLOAD_HDR.pack(len(payload)) + payload
    )


class _SubWriter:
    """Per-connection outbound queue + writer thread. Routing threads
    enqueue and move on; only THIS thread ever blocks on the subscriber's
    socket, so one wedged consumer cannot stall routing from any
    publisher (a write lock per connection would hold the publisher's
    reader thread hostage)."""

    def __init__(self, conn: socket.socket, on_dead):
        self.conn = conn
        self._on_dead = on_dead
        self._q: queue.Queue[bytes | None] = queue.Queue(
            maxsize=_SUB_QUEUE_MAX
        )
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def offer(self, data: bytes) -> bool:
        """Enqueue without blocking; a full queue means the consumer is
        wedged — report failure so the router drops it (QoS 0)."""
        try:
            self._q.put_nowait(data)
            return True
        except queue.Full:
            return False

    def close(self) -> None:
        # sentinel, not queue teardown: the writer drains what it can,
        # then exits; put_nowait keeps close() non-blocking on a full
        # queue (the writer is stuck anyway — its socket is being closed)
        try:
            self._q.put_nowait(None)
        except queue.Full:
            pass

    def _run(self) -> None:
        try:
            self.conn.settimeout(_SUB_SEND_TIMEOUT_S)
        except OSError:
            pass
        while True:
            data = self._q.get()
            if data is None:
                return
            try:
                self.conn.sendall(data)
            except OSError:  # includes socket.timeout: wedged consumer
                self._on_dead(self.conn)
                return


class BrokerDaemon:
    """Topic router. One reader thread per connection; outbound frames go
    through per-subscriber send queues (:class:`_SubWriter`), so a slow or
    stuck subscriber is dropped instead of stalling the router."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._srv = socket.create_server((host, port))
        self._srv.settimeout(0.5)
        self.host, self.port = self._srv.getsockname()[:2]
        self._subs: dict[str, list[socket.socket]] = {}
        self._writers: dict[socket.socket, _SubWriter] = {}
        self._lock = threading.Lock()
        self._stopped = threading.Event()
        self._accept_thread: threading.Thread | None = None
        # keyed by connection and pruned in _drop, so a long-lived
        # broker serving reconnecting clients doesn't accumulate one
        # dead Thread object per historical connection
        self._readers: dict[socket.socket, threading.Thread] = {}

    def start(self) -> "BrokerDaemon":
        t = threading.Thread(target=self._accept_loop, daemon=True)
        t.start()
        self._accept_thread = t
        return self

    def serve_forever(self) -> None:
        self.start()
        self._stopped.wait()

    def _accept_loop(self) -> None:
        while not self._stopped.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(
                target=self._client_loop, args=(conn,), daemon=True
            )
            with self._lock:
                self._writers[conn] = _SubWriter(conn, self._drop)
                self._readers[conn] = t
            t.start()

    def _client_loop(self, conn: socket.socket) -> None:
        try:
            while not self._stopped.is_set():
                frame = _read_frame(conn)
                if frame is None:
                    return
                op, topic, payload = frame
                if op == _OP_SUB:
                    with self._lock:
                        subs = self._subs.setdefault(topic, [])
                        # dedupe: a client that reconnects replays its
                        # subscriptions AND may retry the triggering SUB
                        # frame; a doubled entry would deliver every
                        # publish twice for the rest of the run
                        if conn not in subs:
                            subs.append(conn)
                elif op == _OP_PUB:
                    self._route(topic, payload)
        finally:
            self._drop(conn)

    def _route(self, topic: str, payload: bytes) -> None:
        with self._lock:
            subs = list(self._subs.get(topic, ()))
        data = _frame(_OP_PUB, topic, payload)
        for s in subs:
            with self._lock:
                writer = self._writers.get(s)
            if writer is None:
                continue
            if not writer.offer(data):
                # queue full: the consumer stopped draining long ago —
                # cut it loose so the rest of the world keeps routing
                self._drop(s)

    def _drop(self, conn: socket.socket) -> None:
        with self._lock:
            writer = self._writers.pop(conn, None)
            self._readers.pop(conn, None)
            for subs in self._subs.values():
                while conn in subs:
                    subs.remove(conn)
        if writer is not None:
            writer.close()
        try:
            conn.close()
        except OSError:
            pass

    def stop(self) -> None:
        self._stopped.set()
        self._srv.close()
        # close every live connection: reader threads blocked in recv()
        # unblock instead of lingering into interpreter shutdown (daemon
        # threads inside recv at finalization are a segfault factory)
        with self._lock:
            conns = list(self._writers)
            readers = list(self._readers.values())
        for conn in conns:
            self._drop(conn)
        for t in readers:
            if t is not threading.current_thread():
                t.join(timeout=2.0)


class RemoteTopicBus:
    """Client side of the broker: the ``TopicBus`` contract over one TCP
    connection. Callbacks run on the bus's reader thread (paho's
    ``loop_start`` network thread calling ``on_message``).

    Connect uses the shared exponential-backoff policy (the broker may
    still be starting); a send that hits a dead socket transparently
    re-dials and replays the topic subscriptions — paho's
    ``reconnect_on_failure`` behavior, which the reference's MQTT path
    gets for free from the library."""

    def __init__(
        self, host: str, port: int, connect_timeout: float = 10.0
    ):
        self.host, self.port = host, port
        self._connect_policy = RetryPolicy(
            max_attempts=1000, base_delay_s=0.1, max_delay_s=1.0,
            deadline_s=connect_timeout,
        )
        self._cbs: dict[str, list[Callable[[str, bytes], None]]] = {}
        self._lock = threading.Lock()
        self._wlock = threading.Lock()
        self._stopped = threading.Event()
        self._reader: threading.Thread | None = None
        self._sock: socket.socket | None = None
        with self._wlock:
            self._dial_locked()

    def _dial_locked(self) -> None:
        """(Re)connect + replay subscriptions + restart the reader.
        Caller holds ``_wlock``."""
        last_err: Exception | None = None
        # per-process jitter seed: after a broker restart, N clients
        # must not retry in lockstep waves against the recovering daemon
        for _ in iter_attempts(self._connect_policy, seed=os.getpid(),
                               stop=self._stopped):
            try:
                self._sock = socket.create_connection(
                    (self.host, self.port), timeout=5
                )
                break
            except OSError as err:  # broker may still be starting
                last_err = err
        else:
            raise RetryExhausted(
                f"broker {self.host}:{self.port} unreachable: {last_err}"
            ) from last_err
        self._sock.settimeout(None)
        with self._lock:
            topics = list(self._cbs)
        for topic in topics:  # replay subscriptions on the new conn
            self._sock.sendall(_frame(_OP_SUB, topic))
        # the previous reader (if any) exits on its dead socket
        self._reader = threading.Thread(
            target=self._read_loop, args=(self._sock,), daemon=True
        )
        self._reader.start()

    def _send_frame(self, data: bytes) -> None:
        with self._wlock:
            last: Exception | None = None
            for attempt in range(3):
                if attempt:
                    # redial can itself die mid-handshake (broker
                    # flapping): the SUB replay inside _dial_locked and
                    # the resend below stay inside this loop so no bare
                    # OSError escapes to publish()/subscribe() callers
                    telemetry.METRICS.inc("transport.reconnects")
                    telemetry.RECORDER.record(
                        "broker_redial",
                        broker=f"{self.host}:{self.port}",
                    )
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    try:
                        self._dial_locked()
                    except RetryExhausted:
                        raise  # broker unreachable: fail loudly now
                    except OSError as err:
                        last = err  # flapped mid-handshake: try again
                        continue
                    if data[:1] == _OP_SUB:
                        # the redial already replayed every subscription
                        # (including the one this frame carries) —
                        # resending would double-subscribe
                        return
                try:
                    # fedlint: disable=lock-hygiene  _wlock IS the
                    # frame serializer: one socket, whole frames — a
                    # send outside it could interleave with a redial's
                    # SUB replay and corrupt the stream. Nothing else
                    # ever waits on _wlock holders (publish/subscribe
                    # are the only takers), so the block is bounded by
                    # the socket timeout, not a deadlock risk.
                    self._sock.sendall(data)
                    return
                except OSError as err:
                    if self._stopped.is_set():
                        raise
                    last = err
            raise RetryExhausted(
                f"publish to broker {self.host}:{self.port} failed "
                f"after reconnects: {last!r}"
            ) from last

    def subscribe(self, topic: str, callback: Callable[[str, bytes], None]):
        first = False
        with self._lock:
            cbs = self._cbs.setdefault(topic, [])
            first = not cbs
            cbs.append(callback)
        if first:  # one broker-side subscription per topic per process
            self._send_frame(_frame(_OP_SUB, topic))

    def publish(self, topic: str, payload: bytes) -> None:
        self._send_frame(_frame(_OP_PUB, topic, payload))

    def _read_loop(self, sock: socket.socket) -> None:
        while not self._stopped.is_set():
            frame = _read_frame(sock)
            if frame is None:
                return
            _, topic, payload = frame
            with self._lock:
                cbs = list(self._cbs.get(topic, ()))
            for cb in cbs:
                cb(topic, payload)

    def close(self) -> None:
        self._stopped.set()
        try:
            self._sock.close()
        except OSError:
            pass
        if (self._reader is not None
                and self._reader is not threading.current_thread()):
            self._reader.join(timeout=2.0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="fedml_tpu pub/sub broker")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=29950)
    a = p.parse_args(argv)
    daemon = BrokerDaemon(a.host, a.port)
    print(f"broker listening on {daemon.host}:{daemon.port}", flush=True)
    daemon.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
