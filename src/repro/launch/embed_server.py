"""Threaded TCP embedding server — one listener per shard.

The live counterpart of the paper's Redis instance (§5.1): a process
that owns one :class:`~repro.core.embedding_server.EmbeddingServer`
table set and serves ``register`` / ``write`` / ``gather`` over the
length-prefixed binary protocol in :mod:`repro.exchange.wire`.  Codec
payloads (fp32 / fp16 / int8+scales) travel as the actual bytes the
analytic :class:`NetworkModel` charges for, so modelled and measured
network time can finally be calibrated against each other
(``benchmarks/bench_wire.py``).

Topology: run S listeners (one per shard) and point
:class:`repro.exchange.socket_transport.TcpTransport` at all of them —
the client hashes vertex ids across shards exactly like
``ShardedTransport``, so the stored state is bit-identical to the
in-process transports.

Concurrency: one accept loop + one thread per connection; requests on a
single connection are answered in arrival order (pipelining-safe), and
a lock serialises table access across connections.

CLI (one shard)::

    python -m repro.launch.embed_server --port 7040 \
        --num-layers 3 --hidden 32

Tests and benchmarks use :func:`serve_in_thread`, which binds an
ephemeral port and returns a stoppable handle.
"""

from __future__ import annotations

import argparse
import socket
import threading

import numpy as np

from repro.core.embedding_server import EmbeddingServer
from repro.exchange import wire
from repro.exchange.codec import get_codec
from repro.launch.chip import announce_device, enable_compile_cache, pin_cpu
from repro.obsv import teleserve
from repro.obsv.metrics import REGISTRY
from repro.obsv.trace import TRACE

_REQS = REGISTRY.counter("embed.requests")
_OP_SPAN = {wire.OP_REGISTER: "embed.register", wire.OP_WRITE: "embed.write",
            wire.OP_GATHER: "embed.gather", wire.OP_VGATHER: "embed.vgather",
            wire.OP_EMBED_STATS: "embed.stats"}


class _ServerState:
    """Shared state of one listener: the tables + their lock."""

    def __init__(self, num_layers: int, hidden: int, *,
                 device_tables: bool = False):
        self.store = EmbeddingServer(num_layers, hidden,    # guarded-by: self.lock
                                     device_tables=device_tables)
        self.lock = threading.Lock()
        self.stop = threading.Event()

    def handle(self, body: bytes) -> bytes:
        """One request body → one response body (never raises)."""
        telemetry = teleserve.handle_telemetry(body)
        if telemetry is not None:
            return telemetry
        try:
            op, req = wire.parse_request(body)
        except Exception as e:                              # malformed frame
            return wire.build_err(f"bad request: {type(e).__name__}: {e}")
        _REQS.inc()
        # bounded: every value in _OP_SPAN is a literal span name
        with TRACE.span(_OP_SPAN.get(op, "embed.op")):  # repro-lint: disable=TL001
            return self._dispatch(op, req)

    def _dispatch(self, op: int, req: dict) -> bytes:
        try:
            if op == wire.OP_REGISTER:
                with self.lock:
                    self.store.register(req["global_ids"])
                return wire.build_ok()
            if op == wire.OP_WRITE:
                return self._handle_write(req)
            if op == wire.OP_GATHER:
                return self._handle_gather(req)
            if op == wire.OP_VGATHER:
                return self._handle_vgather(req)
            if op == wire.OP_EMBED_STATS:
                with self.lock:
                    payload = wire.build_stats_payload(
                        self.store.L, self.store.hidden,
                        len(self.store._row), self.store.memory_bytes())
                return wire.build_ok(payload)
            if op == wire.OP_EMBED_SHUTDOWN:
                self.stop.set()
                return wire.build_ok()
            return wire.build_err(f"unknown opcode {op}")
        except Exception as e:
            return wire.build_err(f"{type(e).__name__}: {e}")

    def _handle_write(self, req: dict) -> bytes:
        codec, gids = req["codec"], req["global_ids"]
        with self.lock:     # geometry reads; decode work stays unlocked
            hidden, num_layers = self.store.hidden, self.store.L
            on_device = self.store.device_tables
        n = len(gids)
        if req["num_blocks"] != num_layers - 1:
            return wire.build_err(
                f"write carries {req['num_blocks']} layer blocks, server "
                f"stores {num_layers - 1}")
        cdc = get_codec(codec)
        block = wire.payload_nbytes(codec, n, hidden)
        buf, values = req["payload"], []
        if len(buf) != block * req["num_blocks"]:
            return wire.build_err(
                f"write payload is {len(buf)} B, expected "
                f"{block * req['num_blocks']} B "
                f"({req['num_blocks']}×{block})")
        fused = codec == "int8" and on_device
        for l in range(req["num_blocks"]):
            payload = wire.decode_block(codec, buf[l * block:(l + 1) * block],
                                        n, hidden)
            if fused:
                # ship the wire form straight to the fused decode+scatter
                # — the payload crosses host→device exactly once
                values.append(tuple(np.ascontiguousarray(p)
                                    for p in payload))
            else:
                values.append(np.asarray(cdc.decode(payload), np.float32))
        with self.lock:
            if fused:
                self.store.write_quantized(gids, values)
            else:
                self.store.write(gids, values)
        return wire.build_ok()

    def _handle_gather(self, req: dict) -> bytes:
        codec, gids = req["codec"], req["global_ids"]
        cdc = get_codec(codec)
        with self.lock:
            if codec == "int8" and self.store.device_tables:
                # fused gather+encode on the resident table; the
                # device→host crossing happens once, inside
                # encode_block's tobytes
                payloads = self.store.gather_quantized(gids, req["layers"])
                rows = None
            else:
                payloads = None
                rows = self.store.gather(gids, req["layers"])
        # gather returns fresh copies, so encoding runs unlocked
        if payloads is not None:
            blocks = [wire.encode_block(codec, p) for p in payloads]
        else:
            blocks = [wire.encode_block(codec, cdc.encode(r)) for r in rows]
        return wire.build_ok(b"".join(blocks))

    def _handle_vgather(self, req: dict) -> bytes:
        codec, gids = req["codec"], req["global_ids"]
        cdc = get_codec(codec)
        with self.lock:
            ver, _stale, vals = self.store.gather_if_stale(
                gids, req["have_versions"], req["layers"])
        blocks = [wire.encode_block(codec, cdc.encode(r)) for r in vals]
        return wire.build_ok(ver.tobytes() + b"".join(blocks))


class EmbedServerHandle:
    """A running listener: address for clients, ``stop()`` for teardown."""

    def __init__(self, state: _ServerState, sock: socket.socket,
                 thread: threading.Thread):
        self._state = state
        self._sock = sock
        self._thread = thread
        self.host, self.port = sock.getsockname()[:2]

    @property
    def address(self) -> tuple[str, int]:
        return (self.host, self.port)

    @property
    def store(self) -> EmbeddingServer:
        return self._state.store

    def stop(self, timeout: float = 5.0) -> None:
        self._state.stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        self._thread.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


def _client_loop(conn: socket.socket, state: _ServerState) -> None:
    try:
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        while not state.stop.is_set():
            body = wire.recv_frame(conn)
            if body is None:
                break
            wire.send_frame(conn, state.handle(body))
    except (ConnectionError, OSError):
        pass                                      # client went away
    finally:
        try:
            conn.close()
        except OSError:
            pass


def _accept_loop(listener: socket.socket, state: _ServerState) -> None:
    listener.settimeout(0.2)                      # poll the stop flag
    threads: list[threading.Thread] = []
    while not state.stop.is_set():
        try:
            conn, _ = listener.accept()
        except socket.timeout:
            continue
        except OSError:
            break                                 # listener closed
        t = threading.Thread(target=_client_loop, args=(conn, state),
                             daemon=True)
        t.start()
        threads.append(t)
    try:
        listener.close()
    except OSError:
        pass
    for t in threads:
        t.join(0.5)


def serve_in_thread(num_layers: int, hidden: int, *,
                    host: str = "127.0.0.1",
                    port: int = 0,
                    device_tables: bool = False) -> EmbedServerHandle:
    """Start one shard listener on a background thread (ephemeral port
    by default) and return its handle."""
    state = _ServerState(num_layers, hidden, device_tables=device_tables)
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind((host, port))
    listener.listen(64)
    thread = threading.Thread(target=_accept_loop, args=(listener, state),
                              daemon=True)
    thread.start()
    return EmbedServerHandle(state, listener, thread)


def serve(num_layers: int, hidden: int, *, host: str = "127.0.0.1",
          port: int = 7040, device_tables: bool = False) -> None:
    """Blocking single-shard server (the CLI entrypoint)."""
    handle = serve_in_thread(num_layers, hidden, host=host, port=port,
                             device_tables=device_tables)
    TRACE.set_process(f"embed_server:{handle.port}")
    print(f"embed_server listening on {handle.host}:{handle.port} "
          f"(L={num_layers}, hidden={hidden}"
          f"{', device tables' if device_tables else ''})", flush=True)
    try:
        while not handle._state.stop.is_set():
            handle._state.stop.wait(0.5)
    except KeyboardInterrupt:
        pass
    finally:
        handle.stop()


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description="TCP embedding-server shard (repro.exchange wire "
                    "protocol)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=7040)
    ap.add_argument("--num-layers", type=int, default=3,
                    help="GNN depth L; the server stores L-1 tables")
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--device-tables", action="store_true",
                    help="hold the layer tables as device (jax) arrays "
                         "and serve int8 gathers/writes through the "
                         "fused kernels (bit-identical values)")
    args = ap.parse_args(argv)
    if args.device_tables:      # the tables live on the chip
        enable_compile_cache()
        announce_device("embed_server")
    else:                       # host tables: keep off the chip
        pin_cpu()
    serve(args.num_layers, args.hidden, host=args.host, port=args.port,
          device_tables=args.device_tables)


if __name__ == "__main__":
    main()
