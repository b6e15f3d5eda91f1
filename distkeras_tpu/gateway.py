"""Serving gateway — multi-replica routing, failover, and rolling
weight updates in front of N ``DecodeEngine``s.

``DecodeEngine`` is deliberately single-driver: one thread steps the
compiled programs, and the engine's own lock only makes ``submit``
safe, not ``step``.  That leaves three production gaps this module
closes (the serving-side mirror of what ``ResilientPSClient`` /
``PSServer.restart_from`` already give the training side):

* **Routing** — ``ServingGateway`` spreads requests over K replicas
  under a pluggable policy: ``round_robin`` (fair under uniform
  traffic), ``least_loaded`` (queue-depth + slot-occupancy aware,
  breaking ties on the paged engines' ``free_pages`` headroom so
  paged replicas absorb bursts first — envelope replicas fall back
  to queue depth alone; the right default under ragged decode
  lengths), or ``session`` (sticky key-hash affinity, so a
  conversation keeps hitting the replica that holds its KV prefix
  warm).
* **Failover** — a replica erroring, shedding, or dying mid-stream
  does not fail the request: the gateway reschedules it onto another
  replica under the same seeded full-jitter backoff discipline as
  ``ResilientPSClient``, and first-completion-wins futures make
  delivery exactly-once even when a timed-out attempt later limps
  home.  Each engine's in-flight ``request_id`` dedupe keeps a single
  engine at-most-once; a killed replica's in-flight requests complete
  elsewhere (the chaos test pins this).
* **Rolling weight updates** — ``rolling_update(source)`` pulls new
  weights from a live parameter server (``HostParameterServer`` /
  ``ShardedParameterServer`` / a PS client), a PS snapshot file
  (``checkpoint.ps_snapshot_center``), or a raw pytree, then drains
  and hot-swaps ONE replica at a time (``DecodeEngine.
  swap_variables`` — same treedef/shapes, zero recompiles) while the
  others keep serving.  After each swap the replica's health is
  re-checked; a ``critical`` verdict rolls every already-updated
  replica back to the pre-rollout weights.

Replica arms:

* ``EngineReplica`` — in-process: wraps one engine with its own
  driver thread and a mailbox, so submission is thread-safe by
  construction and weight swaps land exactly at step boundaries.
* ``ReplicaServer`` / ``RemoteReplica`` — the socket arm: the same
  replica served over ``parallel.transport`` framing (msgpack
  payloads via ``pack_obj``, never pickle), with ``trace_header()``
  propagation so gateway→replica spans pair up in a merged Perfetto
  timeline, and the ``parallel.faults.ChaosTransport`` choke point in
  the path (``target_ports={replica_port}`` attacks just this hop).
  ``ReplicaServer.kill()`` severs the wire AND the driver — the crash
  the failover machinery exists for.

Observability: ``gateway_requests_total{replica,policy}`` /
``gateway_failovers_total{replica}`` counters (their ratio is the
watchdog's ``failover_rate`` signal), swap/rollout spans, and flight-
recorder events — ``replica_down``, ``failover``, ``weight_swap``,
``rollback`` — so a postmortem can replay a rollout or a crash story
from disk.  ``healthz()`` aggregates per-replica verdicts into one
gateway state.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import queue
import socket
import threading
import zlib
from typing import Any, Callable, Iterable, Iterator, Mapping, Optional

import jax
import numpy as np

from distkeras_tpu import flight_recorder, paging, telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.parallel import transport
from distkeras_tpu.serving import (ShedError, pack_kv_blocks,
                                   unpack_kv_blocks)

_UNSET = object()

POLICIES = ("round_robin", "least_loaded", "session", "prefix")


class ReplicaDown(ConnectionError):
    """The addressed replica is dead (driver crashed, socket severed,
    or stopped) — the gateway's cue to fail the attempt over.  A
    ``ConnectionError`` subclass so transport-level and replica-level
    failures share one retry classification."""


class _Future:
    """First-completion-wins result cell: ``set`` returns True only
    for the first caller, so a late duplicate (a timed-out attempt
    completing after its failover already won) is dropped — delivery
    is exactly-once even when execution was not."""

    __slots__ = ("_lock", "_event", "_result", "_set")

    def __init__(self):
        self._lock = racecheck.lock("gateway.future")
        self._event = threading.Event()
        self._result = None
        self._set = False

    def set(self, result) -> bool:
        with self._lock:
            if self._set:
                return False
            self._set = True
            self._result = result
        self._event.set()
        return True

    def ready(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("result not ready")
        return self._result


# ---------------------------------------------------------------------
# in-process replica: one engine, one driver thread
# ---------------------------------------------------------------------


class EngineReplica:
    """One ``DecodeEngine`` plus its own driver thread.

    All interaction goes through a mailbox the driver consumes between
    step quanta: ``dispatch`` enqueues a request (callback-style
    completion), ``swap`` enqueues a weight swap (so it executes at a
    step boundary by construction — the driver never holds a step
    half-done), ``quiesce`` blocks until nothing is queued or live.
    The engine itself is never touched from another thread, which is
    exactly the threading contract ``DecodeEngine.step`` demands.

    A driver crash (poisoned engine, injected kill) marks the replica
    down, records a ``replica_down`` flight event, and fails every
    pending request with ``ReplicaDown`` — the gateway then reroutes
    them.  ``stop()`` is the graceful variant: in-flight requests come
    back as the engine's ``error="engine_closed"`` results (which the
    gateway also treats as failover-able, so stopping one replica for
    maintenance loses nothing).
    """

    def __init__(self, engine, name: str = "replica0"):
        self.engine = engine
        self.name = str(name)
        # RLock'd condition: load() re-enters from quiesce's wait loop
        self._cv = racecheck.condition("gateway.replica_cv")
        self._mailbox: collections.deque = collections.deque()
        self._pending: dict[Any, Callable] = {}
        self._alive = False  # guarded-by: _cv
        self._stop_req = False
        self._killed = False
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "EngineReplica":
        if self._thread is not None:
            return self
        with self._cv:  # health() may race the spawn below
            self._alive = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"dkt-replica-{self.name}")
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Graceful shutdown: the driver exits, the engine is closed,
        and in-flight requests are delivered as ``engine_closed``
        error results (never silently dropped)."""
        with self._cv:
            self._stop_req = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)

    def kill(self) -> None:
        """Crash simulation: the driver dies at its next loop top as
        if the process had — pending requests fail with
        ``ReplicaDown`` and the gateway's failover takes over."""
        with self._cv:
            self._killed = True
            self._cv.notify_all()

    @property
    def alive(self) -> bool:
        return self._alive

    # -- gateway-facing surface ---------------------------------------

    def load(self) -> int:
        """Requests owned by this replica (queued in the mailbox or in
        the engine) — the ``least_loaded`` routing signal."""
        with self._cv:
            return len(self._pending) + sum(
                1 for c in self._mailbox if c[0] == "submit")

    def free_pages(self) -> Optional[int]:
        """Free device KV pages on a paged engine (``None``: envelope
        pools) — ``least_loaded``'s tie-break signal."""
        fn = getattr(self.engine, "free_pages", None)
        return fn() if callable(fn) else None

    def dispatch(self, spec: Mapping, on_result: Callable) -> None:
        """Enqueue one request; ``on_result(result_or_exception)``
        fires exactly once from the driver thread."""
        with self._cv:
            if not self._alive:
                raise ReplicaDown(f"replica {self.name} is down")
            self._mailbox.append(("submit", dict(spec), on_result))
            self._cv.notify_all()

    def swap(self, variables: Mapping,
             timeout: float = 60.0) -> None:
        """Install new weights at the next step boundary (blocks until
        the driver has executed the swap); raises on mismatch."""
        fut = _Future()
        with self._cv:
            if not self._alive:
                raise ReplicaDown(f"replica {self.name} is down")
            self._mailbox.append(("swap", variables, fut.set))
            self._cv.notify_all()
        res = fut.wait(timeout)
        if isinstance(res, Exception):
            raise res

    def _kv_call(self, op: str, payload, timeout: float):
        """Run one prefix-store interchange op on the DRIVER thread
        (the store's ownership discipline — see ``DecodeEngine.
        export_prefix``) and block for its result."""
        fut = _Future()
        with self._cv:
            if not self._alive:
                raise ReplicaDown(f"replica {self.name} is down")
            self._mailbox.append(("kv", (op, payload), fut.set))
            self._cv.notify_all()
        res = fut.wait(timeout)
        if isinstance(res, Exception):
            raise res
        return res

    def kv_probe(self, prompt, timeout: float = 60.0) -> int:
        """Leading prompt blocks the engine's prefix store already
        holds (the router's ship-only-what's-missing check)."""
        return self._kv_call("probe", prompt, timeout)

    def kv_export(self, prompt, timeout: float = 60.0):
        """The engine's cached prefix blocks for ``prompt`` as a host
        export dict (``None``: nothing cached) — the prefill side of
        the disaggregated handoff."""
        return self._kv_call("export", prompt, timeout)

    def kv_import(self, export: Mapping,
                  timeout: float = 60.0) -> int:
        """Install a shipped block set into the engine's prefix store;
        returns blocks newly installed — the decode side of the
        handoff."""
        return self._kv_call("import", export, timeout)

    def variables(self) -> Mapping:
        """The engine's current weights (read-only use: the rollback
        snapshot).  Safe without the driver — ``swap_variables``
        replaces the whole dict atomically under the engine lock."""
        return self.engine.variables

    def quiesce(self, timeout: float = 60.0) -> None:
        """Block until the replica holds no work (the drain step of a
        rolling update — the gateway stops routing here first)."""
        deadline = telemetry.now() + timeout
        with self._cv:
            while self.load() > 0:
                left = deadline - telemetry.now()
                if left <= 0:
                    raise TimeoutError(
                        f"replica {self.name} did not quiesce within "
                        f"{timeout}s ({self.load()} in flight)")
                self._cv.wait(min(left, 0.1))

    def health(self) -> dict:
        """Liveness + load + the engine's SLO verdict."""
        if not self._alive:
            return {"alive": False, "state": "down", "load": 0}
        return {"alive": True, "load": self.load(),
                "free_pages": self.free_pages(),
                **self.engine.health()}

    # -- driver -------------------------------------------------------

    def _loop(self) -> None:
        eng = self.engine
        try:
            while True:
                with self._cv:
                    while (not self._mailbox and not self._stop_req
                           and not self._killed
                           and not eng.has_work()):
                        # bounded wait: has_work() can also change via
                        # the engine's own deadline clock
                        self._cv.wait(0.05)
                    if self._killed:
                        raise ReplicaDown(
                            f"replica {self.name}: killed")
                    if self._stop_req:
                        break
                    cmds = list(self._mailbox)
                    self._mailbox.clear()
                for cmd in cmds:
                    self._exec(cmd)
                if eng.has_work():
                    for res in eng.step():
                        self._deliver(res)
                with self._cv:
                    self._cv.notify_all()  # wake quiesce()
        except BaseException as e:  # driver death == replica death
            self._die(e)
            return
        self._shutdown()

    def _exec(self, cmd) -> None:
        if cmd[0] == "swap":
            _, variables, done = cmd
            try:
                self.engine.swap_variables(variables)
                done(None)
            except Exception as e:
                done(e)
            return
        if cmd[0] == "kv":
            _, (op, payload), done = cmd
            try:
                if op == "probe":
                    done(self.engine.match_blocks(payload))
                elif op == "export":
                    done(self.engine.export_prefix(payload))
                else:
                    done(self.engine.import_prefix(
                        payload["prompt"], payload["blocks"],
                        payload.get("weights_ver")))
            except Exception as e:
                done(e)
            return
        _, spec, cb = cmd
        kwargs = {}
        for k in ("max_new_tokens", "eos_id", "deadline", "meta",
                  "tenant", "priority", "speculative"):
            if k in spec:
                kwargs[k] = spec[k]
        try:
            rid = self.engine.submit(spec["prompt"],
                                     request_id=spec["request_id"],
                                     **kwargs)
        except Exception as e:  # ShedError, validation, closed engine
            cb(e)
            return
        with self._cv:
            self._pending[rid] = cb

    def _deliver(self, res: dict) -> None:
        with self._cv:
            cb = self._pending.pop(res["request_id"], None)
            if not self._pending and not self._mailbox:
                self._cv.notify_all()
        if cb is not None:
            cb(res)

    def _take_all(self) -> tuple[dict, list]:
        with self._cv:
            self._alive = False
            pending, self._pending = self._pending, {}
            cmds = list(self._mailbox)
            self._mailbox.clear()
            self._cv.notify_all()
        return pending, cmds

    def _fail_cmds(self, cmds, exc: Exception) -> None:
        # both command kinds carry their callback third; both accept
        # an exception as the terminal outcome
        for cmd in cmds:
            with contextlib.suppress(Exception):
                cmd[2](exc)

    def _die(self, exc: BaseException) -> None:
        pending, cmds = self._take_all()
        telemetry.metrics().counter("gateway_replica_down_total",
                                    replica=self.name).inc()
        flight_recorder.record("replica_down", replica=self.name,
                               error=repr(exc))
        flight_recorder.flush()
        with contextlib.suppress(Exception):
            self.engine.close()  # release pools; results irrelevant
        down = ReplicaDown(f"replica {self.name} died: {exc!r}")
        for cb in pending.values():
            with contextlib.suppress(Exception):
                cb(down)
        self._fail_cmds(cmds, down)

    def _shutdown(self) -> None:
        pending, cmds = self._take_all()
        try:
            results = {r["request_id"]: r
                       for r in self.engine.close()}
        except Exception:
            results = {}
        down = ReplicaDown(f"replica {self.name} stopped")
        for rid, cb in pending.items():
            with contextlib.suppress(Exception):
                cb(results.get(rid, down))
        self._fail_cmds(cmds, down)


# ---------------------------------------------------------------------
# socket arm
# ---------------------------------------------------------------------
#
# Protocol (every message framed by ``transport``, an optional 17-byte
# trace-context header first, then a command byte):
#   b"g" + pack_obj(spec)      -> pack_obj(result dict)   (generate)
#   b"h"                       -> pack_obj(health dict)
#   b"w" + pack_obj(variables) -> pack_obj({"ok"| "error"}) (swap)
#   b"v"                       -> pack_obj(variables)     (rollback src)
#   b"q"                       -> pack_obj({"ok"| "error"}) (quiesce)
#   b"s"                       -> connection closes        (stop server)
#   b"y" + pack_obj(prompt)    -> pack_obj({"blocks"|"error"}) (kv probe)
#   b"x" + pack_obj(prompt)    -> kv page-blocks frame     (kv export)
#   b"k" + kv page-blocks body -> pack_obj({"imported"|"error"})
# Payloads are flax msgpack (``pack_obj``) — self-describing, never
# pickle; a generate connection stays open for the whole request, so a
# severed wire maps 1:1 to a failed attempt.  The kv page-blocks frame
# is ``serving.pack_kv_blocks``'s gather-sent wire form (scope
# ``"kv"``): raw page memoryviews behind a length-prefixed msgpack
# meta, so exported KV never round-trips through msgpack arrays.


def _exc_error(e: Exception) -> str:
    if isinstance(e, ShedError):
        return f"shed: {e}"
    if isinstance(e, ReplicaDown):
        return f"replica_down: {e}"
    return f"replica_error: {e!r}"


class ReplicaServer:
    """Serve one ``EngineReplica`` over the socket transport.

    Mirrors ``PSServer``'s accept-loop shape (daemon handler thread
    per connection, 0.2s accept poll, trace-linked rpc spans), so the
    chaos and tracing machinery built for the PS wire applies
    unchanged to the serving wire.
    """

    def __init__(self, replica: EngineReplica,
                 host: str = "127.0.0.1", port: int = 0):
        self.replica = replica
        self._sock = socket.socket()
        self._sock.setsockopt(socket.SOL_SOCKET,
                              socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen()
        self.address = self._sock.getsockname()
        self._conns: list[socket.socket] = []
        self._stop = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"dkt-replica-srv-{replica.name}")

    def start(self) -> "ReplicaServer":
        self.replica.start()
        self._accept_thread.start()
        return self

    def _accept_loop(self) -> None:
        self._sock.settimeout(0.2)
        try:
            while not self._stop.is_set():
                try:
                    conn, _ = self._sock.accept()
                except socket.timeout:
                    continue
                except OSError:
                    break
                conn.setsockopt(socket.IPPROTO_TCP,
                                socket.TCP_NODELAY, 1)
                self._conns.append(conn)
                threading.Thread(target=self._serve, args=(conn,),
                                 daemon=True).start()
        finally:
            with contextlib.suppress(OSError):
                self._sock.close()

    def _serve(self, conn: socket.socket) -> None:
        with conn:
            try:
                while True:
                    msg = transport.recv_msg(conn)
                    link, msg = transport.split_trace_header(msg)
                    cmd, body = bytes(msg[:1]), msg[1:]
                    with contextlib.ExitStack() as rpc:
                        if link is not None:
                            rpc.enter_context(telemetry.span(
                                "replica_rpc", cmd=cmd.decode(),
                                replica=self.replica.name,
                                link_trace=format(link[0], "x"),
                                link_span=format(link[1], "x")))
                            telemetry.flow_end("wire", link[1],
                                               cmd=cmd.decode())
                        self._dispatch(conn, cmd, body)
                    if self._stop.is_set():
                        return
            except (ConnectionError, OSError):
                return  # client gone / chaos-severed

    def _dispatch(self, conn: socket.socket, cmd: bytes,
                  body: bytes) -> None:
        rep = self.replica
        if cmd == b"g":
            spec = transport.unpack_obj(body)
            spec["prompt"] = np.asarray(spec["prompt"], np.int32)
            fut = _Future()
            try:
                rep.dispatch(spec, fut.set)
                res = fut.wait()
            except Exception as e:
                res = e
            if isinstance(res, Exception):
                res = {"request_id": spec.get("request_id"),
                       "prompt": spec["prompt"],
                       "tokens": np.zeros((0,), np.int32),
                       "error": _exc_error(res)}
            transport.send_msg(conn, transport.pack_obj(
                jax.device_get(res)))
        elif cmd == b"h":
            transport.send_msg(conn,
                               transport.pack_obj(rep.health()))
        elif cmd == b"w":
            try:
                rep.swap(transport.unpack_obj(body))
                out = {"ok": True}
            except Exception as e:
                out = {"error": _exc_error(e)}
            transport.send_msg(conn, transport.pack_obj(out))
        elif cmd == b"v":
            transport.send_msg(conn, transport.pack_obj(
                jax.device_get(rep.variables())))
        elif cmd == b"q":
            try:
                rep.quiesce()
                out = {"ok": True}
            except Exception as e:
                out = {"error": _exc_error(e)}
            transport.send_msg(conn, transport.pack_obj(out))
        elif cmd == b"y":
            prompt = np.asarray(transport.unpack_obj(body), np.int32)
            try:
                out = {"blocks": int(rep.kv_probe(prompt))}
            except Exception as e:
                out = {"error": _exc_error(e)}
            transport.send_msg(conn, transport.pack_obj(out))
        elif cmd == b"x":
            prompt = np.asarray(transport.unpack_obj(body), np.int32)
            try:
                export = rep.kv_export(prompt)
            except Exception:
                export = None  # export is best-effort: reply empty,
                #                the importer recomputes instead
            if export is None:
                export = {"prompt": prompt, "blocks": []}
            transport.send_msg_gather(conn, *pack_kv_blocks(export))
        elif cmd == b"k":
            try:
                out = {"imported": int(rep.kv_import(
                    unpack_kv_blocks(body)))}
            except Exception as e:
                out = {"error": _exc_error(e)}
            transport.send_msg(conn, transport.pack_obj(out))
        elif cmd == b"s":
            self.stop()
        else:
            raise ValueError(f"unknown command {cmd!r}")

    def stop(self) -> None:
        """Graceful: stop accepting; live requests finish; the replica
        (and its engine) shut down cleanly."""
        self._stop.set()
        with contextlib.suppress(OSError):
            self._sock.close()
        self.replica.stop()

    def kill(self) -> None:
        """Crash simulation: sever the listener, every live
        connection, AND the driver — clients see ``ConnectionError``
        mid-frame and the gateway fails their requests over.  The
        flight marker is fsynced first, as on ``PSServer.kill``."""
        flight_recorder.record("replica_down",
                               replica=self.replica.name,
                               error="killed", port=self.address[1])
        flight_recorder.flush(fsync=True)
        self._stop.set()
        for s in (self._sock, *self._conns):
            with contextlib.suppress(OSError):
                s.close()
        self.replica.kill()


class RemoteReplica:
    """Gateway-side proxy for a ``ReplicaServer``.

    Each generate attempt runs on its own dispatch thread over its own
    connection (``trace_header()`` + ``flow_start`` pair the client
    span with the server's ``replica_rpc`` span in a merged trace), so
    a severed wire fails exactly one attempt.  Any transport-level
    failure marks the proxy down — the gateway stops routing here
    until ``probe()`` succeeds again.
    """

    def __init__(self, host: str, port: int,
                 name: Optional[str] = None, *,
                 attempt_timeout: Optional[float] = None,
                 connect_timeout: float = 5.0):
        self.host = host
        self.port = int(port)
        self.name = name if name is not None else f"{host}:{port}"
        self.attempt_timeout = attempt_timeout
        self.connect_timeout = connect_timeout
        self._lock = racecheck.lock("gateway.remote")
        self._alive = True  # guarded-by: _lock
        self._outstanding = 0  # guarded-by: _lock
        self._free_pages = None  # last health-reported page headroom

    def start(self) -> "RemoteReplica":
        return self  # the server owns the engine lifecycle

    @property
    def alive(self) -> bool:
        return self._alive

    def load(self) -> int:
        return self._outstanding

    def free_pages(self) -> Optional[int]:
        """Page headroom as of the last ``health()``/``probe()``
        round-trip (``None`` until one lands, or for envelope-pool
        servers) — a cached snapshot, not a live read: routing must
        not pay an RPC per choice."""
        return self._free_pages

    def _exchange(self, cmd: bytes, body: bytes = b"",
                  timeout: Optional[float] = None):
        # transport.* looked up at call time: the ChaosTransport choke
        # point must see this hop
        sock = transport.connect(self.host, self.port,
                                 timeout=self.connect_timeout)
        try:
            if timeout is not None:
                sock.settimeout(timeout)
            hdr = transport.trace_header()
            transport.send_msg(sock, hdr + cmd, body)
            if hdr:
                ctx = telemetry.current_trace()
                telemetry.flow_start("wire", ctx[1],
                                     cmd=cmd.decode())
            return transport.unpack_obj(transport.recv_msg(sock))
        finally:
            with contextlib.suppress(OSError):
                sock.close()

    def _mark_down(self, exc: Exception) -> None:
        with self._lock:
            was = self._alive
            self._alive = False
        if was:
            telemetry.metrics().counter("gateway_replica_down_total",
                                        replica=self.name).inc()
            flight_recorder.record("replica_down", replica=self.name,
                                   error=repr(exc))
            flight_recorder.flush()

    def probe(self) -> bool:
        """One health round-trip; revives a down-marked proxy when the
        server is reachable again (the warm-restart story)."""
        try:
            out = self._exchange(b"h", timeout=self.connect_timeout)
        except (ConnectionError, OSError, ValueError):
            return False
        with self._lock:  # revival races dispatch's _mark_down
            self._alive = True
            if isinstance(out, Mapping):
                self._free_pages = out.get("free_pages")
        return True

    def dispatch(self, spec: Mapping, on_result: Callable) -> None:
        if not self._alive:
            raise ReplicaDown(f"replica {self.name} is down")
        with self._lock:
            self._outstanding += 1
        threading.Thread(target=self._run_request,
                         args=(dict(spec), on_result),
                         daemon=True).start()

    def _run_request(self, spec: dict, on_result: Callable) -> None:
        try:
            with telemetry.span("gateway_rpc", replica=self.name,
                                request_id=str(spec["request_id"])):
                wire = dict(spec)
                wire["prompt"] = np.asarray(spec["prompt"], np.int32)
                out = self._exchange(
                    b"g", transport.pack_obj(wire),
                    timeout=self.attempt_timeout)
                if isinstance(out.get("tokens"), np.ndarray):
                    out["tokens"] = out["tokens"].astype(np.int32)
        except Exception as e:
            self._mark_down(e)
            out = e
        finally:
            with self._lock:
                self._outstanding -= 1
        on_result(out)

    def swap(self, variables: Mapping,
             timeout: float = 120.0) -> None:
        out = self._exchange(
            b"w", transport.pack_obj(jax.device_get(dict(variables))),
            timeout=timeout)
        if "error" in out:
            raise ValueError(f"remote swap failed: {out['error']}")

    def variables(self) -> Mapping:
        return self._exchange(b"v", timeout=120.0)

    def quiesce(self, timeout: float = 60.0) -> None:
        out = self._exchange(b"q", timeout=timeout)
        if "error" in out:
            raise TimeoutError(
                f"remote quiesce failed: {out['error']}")

    # -- disaggregated prefill/decode handoff -------------------------

    def kv_probe(self, prompt, timeout: float = 60.0) -> int:
        try:
            out = self._exchange(
                b"y",
                transport.pack_obj(np.asarray(prompt, np.int32)),
                timeout=timeout)
        except (ConnectionError, OSError) as e:
            self._mark_down(e)
            raise
        if "error" in out:
            raise ReplicaDown(f"kv_probe failed: {out['error']}")
        return int(out["blocks"])

    def kv_export(self, prompt, timeout: float = 60.0):
        """Pull a prompt's cached KV blocks off the remote replica —
        the reply is the raw kv page-blocks frame (``unpack_kv_blocks``
        decodes it in place on the receive buffer, no msgpack detour
        for the page bytes).  ``None`` when nothing is cached."""
        sock = transport.connect(self.host, self.port,
                                 timeout=self.connect_timeout)
        try:
            sock.settimeout(timeout)
            hdr = transport.trace_header()
            transport.send_msg(
                sock, hdr + b"x",
                transport.pack_obj(np.asarray(prompt, np.int32)))
            export = unpack_kv_blocks(transport.recv_msg_into(sock))
        except (ConnectionError, OSError) as e:
            self._mark_down(e)
            raise
        finally:
            with contextlib.suppress(OSError):
                sock.close()
        return export if export["n_blocks"] else None

    def kv_import(self, export: Mapping,
                  timeout: float = 60.0) -> int:
        """Ship a block set into the remote replica's prefix store —
        ONE gather-sent frame, the page memoryviews riding ``sendmsg``
        with zero send-side copies."""
        sock = transport.connect(self.host, self.port,
                                 timeout=self.connect_timeout)
        try:
            sock.settimeout(timeout)
            hdr = transport.trace_header()
            parts = pack_kv_blocks(export)
            transport.send_msg_gather(sock, hdr + b"k", *parts)
            out = transport.unpack_obj(transport.recv_msg(sock))
        except (ConnectionError, OSError) as e:
            self._mark_down(e)
            raise
        finally:
            with contextlib.suppress(OSError):
                sock.close()
        if "error" in out:
            raise ReplicaDown(f"kv_import failed: {out['error']}")
        return int(out["imported"])

    def health(self) -> dict:
        try:
            out = self._exchange(b"h",
                                 timeout=self.connect_timeout)
        except (ConnectionError, OSError, ValueError):
            return {"alive": False, "state": "down", "load": 0}
        if isinstance(out, Mapping):
            with self._lock:
                self._free_pages = out.get("free_pages")
        return out

    def stop_server(self) -> None:
        with contextlib.suppress(ConnectionError, OSError):
            sock = transport.connect(self.host, self.port,
                                     timeout=self.connect_timeout)
            try:
                transport.send_msg(sock, b"s")
            finally:
                with contextlib.suppress(OSError):
                    sock.close()


# ---------------------------------------------------------------------
# the gateway
# ---------------------------------------------------------------------


class _GwRequest:
    __slots__ = ("rid", "spec", "future", "attempts", "tried")

    def __init__(self, rid, spec):
        self.rid = rid
        self.spec = spec
        self.future = _Future()
        self.attempts = 0  # failed attempts so far
        self.tried: set = set()  # replica names already tried


def _classify(res) -> str:
    """``final`` (deliver as-is), ``failover`` (replica failed — count
    + reroute), or ``shed`` (backpressure — retry after backoff
    without calling it a failover)."""
    if isinstance(res, ShedError):
        return "shed"
    if isinstance(res, (ReplicaDown, ConnectionError, OSError,
                        TimeoutError)):
        return "failover"
    if isinstance(res, ValueError) and "in flight" in str(res):
        # the id is still live on that engine (a slow attempt we
        # failed over from) — route elsewhere, don't fail the request
        return "failover"
    if isinstance(res, Exception):
        return "final"
    err = res.get("error")
    if err is None:
        return "final"
    err = str(err)
    if err.startswith("shed"):
        return "shed"
    if err.startswith(("replica_down", "engine_closed")):
        return "failover"
    return "final"  # deadline_exceeded, prefill_failed, replica_error


def _cause(res) -> str:
    return repr(res) if isinstance(res, Exception) \
        else str(res.get("error"))


def _free_pages(rep) -> Optional[int]:
    """A replica's page headroom, ``None`` for envelope replicas (or
    anything not exposing the signal) — the shared routing probe."""
    fn = getattr(rep, "free_pages", None)
    return fn() if callable(fn) else None


class ServingGateway:
    """Route requests over replicas; fail over; roll weights.

    Args:
      replicas: ``EngineReplica`` / ``RemoteReplica`` instances (or
        anything duck-typing their surface).  Names must be unique.
      policy: ``round_robin`` | ``least_loaded`` | ``session`` (sticky
        by the ``session=`` key passed to ``submit``; requests without
        a session key fall back to round-robin) | ``prefix`` (sticky
        by the first ``prefix_block`` prompt tokens, so requests that
        share a system prompt land on the replica whose prefix cache
        is warm — the RadixAttention affinity idea at gateway level;
        composes with failover: a dead replica's key range just hashes
        over the survivors).
      prefix_block: prompt-head length (tokens) hashed by the
        ``prefix`` policy; align it with the engines'
        ``prefill_align`` so requests that share a cacheable prefix
        share a replica.
      retries: failed attempts per request beyond the first before the
        request is completed as ``error="gateway_retries_exhausted"``.
      backoff_base/backoff_max/jitter/seed: full-jitter exponential
        backoff between attempts — the ``ResilientPSClient``
        discipline (``delay = min(max, base * 2**(n-1)) * (1 -
        jitter*u)``), seeded so a chaos sweep's retry timing is
        reproducible.
      deadline: default per-attempt decode budget handed to the
        engine (seconds from engine admission; gateway queue/backoff
        time is NOT counted — each attempt gets a fresh budget).

    Delivery semantics: ``submit`` returns a request id;
    ``result(rid)`` blocks for its single result.  Success results are
    the engine's dicts verbatim; terminal failures come back as
    ``error`` result dicts (never exceptions), matching the engine's
    own error-row contract.  A request is delivered exactly once even
    if two attempts both complete (first wins).
    """

    def __init__(self, replicas: Iterable, *,
                 policy: str = "round_robin", retries: int = 3,
                 backoff_base: float = 0.02, backoff_max: float = 0.5,
                 jitter: float = 0.5, seed: int = 0,
                 deadline: Optional[float] = None,
                 prefix_block: int = 128):
        self._replicas = list(replicas)
        if not self._replicas:
            raise ValueError("ServingGateway needs >= 1 replica")
        names = [r.name for r in self._replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique: {names}")
        if policy not in POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; choose from {POLICIES}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0; got {retries}")
        if not 0.0 <= jitter <= 1.0:
            raise ValueError(f"jitter={jitter} outside [0, 1]")
        if prefix_block < 1:
            raise ValueError(
                f"prefix_block must be >= 1; got {prefix_block}")
        self.policy = policy
        self.prefix_block = int(prefix_block)
        self.retries = int(retries)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.jitter = float(jitter)
        self.deadline = deadline
        self._rng = np.random.default_rng(seed)
        self._lock = racecheck.rlock("gateway")
        self._requests: dict[Any, _GwRequest] = {}
        self._rr = 0  # guarded-by: _lock
        self._n_auto = itertools.count()
        self._seq = itertools.count()  # retry-queue tiebreaker
        self._updating: set = set()  # replica names mid-swap
        self._closing = False  # guarded-by: _lock
        self._started = False  # guarded-by: _lock
        self._retry_q: queue.PriorityQueue = queue.PriorityQueue()
        self._retry_thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "ServingGateway":
        with self._lock:
            if self._started:
                return self
            self._started = True
        for rep in self._replicas:
            rep.start()
        self._retry_thread = threading.Thread(
            target=self._retry_loop, daemon=True,
            name="dkt-gateway-retry")
        self._retry_thread.start()
        return self

    def stop(self) -> None:
        """Shut down: local replicas close their engines (in-flight
        requests complete as ``engine_closed`` error results, without
        failover); remote replica SERVERS are left running — they are
        owned by whoever started them."""
        with self._lock:
            if self._closing:
                return
            self._closing = True
        self._retry_q.put((0.0, -1, None))  # wake + exit
        for rep in self._replicas:
            if isinstance(rep, EngineReplica):
                rep.stop()
        if self._retry_thread is not None:
            self._retry_thread.join(5.0)
        # anything still unresolved (e.g. queued behind a dead retry)
        # is failed out rather than leaking a waiter forever
        with self._lock:
            reqs = list(self._requests.values())
        for req in reqs:
            if not req.future.ready():
                self._complete(req, self._error_result(
                    req, "gateway_closed"))

    def __enter__(self) -> "ServingGateway":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- submission ---------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               eos_id=_UNSET, request_id=None, deadline=_UNSET,
               session=None, meta: Optional[Mapping] = None,
               tenant=None, priority: Optional[int] = None,
               speculative=None, handoff: bool = False):
        """Queue one request; returns its id.  ``session`` is the
        affinity key for the ``session`` policy; ``tenant``/
        ``priority`` ride through to the engine's QoS scheduler
        (inert on envelope-pool replicas); ``speculative`` is the
        per-request speculation override, forwarded only when set
        (replicas without an engine-level ``speculative=`` config
        reject it); ``handoff`` marks a disaggregated decode-side
        dispatch whose KV pages already shipped in, exempting it
        from the page-exhaustion routing exclusion (it never reaches
        the engine).  Explicit ``request_id``s
        must be unique among unresolved gateway requests (and
        msgpack-encodable for remote replicas)."""
        self.start()
        spec: dict = {"prompt": np.asarray(prompt, np.int32)}
        if handoff:
            spec["handoff"] = True
        if max_new_tokens is not None:
            spec["max_new_tokens"] = int(max_new_tokens)
        if eos_id is not _UNSET:
            spec["eos_id"] = eos_id
        dl = self.deadline if deadline is _UNSET else deadline
        if dl is not None:
            spec["deadline"] = float(dl)
        if meta:
            spec["meta"] = dict(meta)
        if session is not None:
            spec["session"] = session
        if tenant is not None:
            spec["tenant"] = tenant
        if priority is not None:
            spec["priority"] = int(priority)
        if speculative is not None:
            spec["speculative"] = bool(speculative)
        with self._lock:
            if self._closing:
                raise RuntimeError("gateway is closed")
            if request_id is None:
                rid = f"gw-{next(self._n_auto)}"
                while rid in self._requests:
                    rid = f"gw-{next(self._n_auto)}"
            else:
                rid = request_id
                if rid in self._requests:
                    raise ValueError(
                        f"request_id {rid!r} is already in flight")
            spec["request_id"] = rid
            req = _GwRequest(rid, spec)
            self._requests[rid] = req
            telemetry.metrics().gauge("gateway_inflight_requests").set(
                len(self._requests))
        self._dispatch(req)
        return rid

    def result(self, request_id, timeout: Optional[float] = None
               ) -> dict:
        """Block for (and consume) one request's result."""
        with self._lock:
            req = self._requests.get(request_id)
        if req is None:
            raise KeyError(f"unknown request_id {request_id!r}")
        res = req.future.wait(timeout)
        with self._lock:
            self._requests.pop(request_id, None)
            telemetry.metrics().gauge("gateway_inflight_requests").set(
                len(self._requests))
        return res

    def try_result(self, request_id):
        """Non-blocking ``result``: the result dict when ready (and
        consumed), else ``None`` with the request left in flight.  The
        traffic simulator's pacing loop polls this between arrivals —
        it must never block behind one slow request while the offered
        load keeps its own clock."""
        with self._lock:
            req = self._requests.get(request_id)
            if req is None:
                raise KeyError(f"unknown request_id {request_id!r}")
            if not req.future.ready():
                return None
            self._requests.pop(request_id, None)
            telemetry.metrics().gauge("gateway_inflight_requests").set(
                len(self._requests))
        return req.future.wait(0)

    def run(self, requests: Iterable, *, ordered: bool = True
            ) -> Iterator[dict]:
        """Serve an iterable to completion — the gateway-level
        ``DecodeEngine.run``.  Items are prompts or mappings with
        ``"prompt"`` (+ ``max_new_tokens``/``eos_id``/``session``/
        ``deadline``/``tenant``/``priority``/``speculative``; other
        keys ride into results as meta).  Engine
        sheds are absorbed by the failover/backoff machinery, so the
        whole iterable is always accounted for: one result per item.
        """
        rids = [self._submit_item(item) for item in requests]
        if ordered:
            for rid in rids:
                yield self.result(rid)
            return
        pending = set(rids)
        while pending:
            done = [rid for rid in pending
                    if self._requests[rid].future.ready()]
            for rid in done:
                pending.discard(rid)
                yield self.result(rid)
            if not done:
                _sleep(0.002)

    def _submit_item(self, item):
        if isinstance(item, Mapping):
            meta = {k: v for k, v in item.items()
                    if k not in ("prompt", "max_new_tokens", "eos_id",
                                 "session", "deadline", "tenant",
                                 "priority", "speculative")}
            return self.submit(
                item["prompt"],
                max_new_tokens=item.get("max_new_tokens"),
                eos_id=item.get("eos_id", _UNSET),
                deadline=item.get("deadline", _UNSET),
                session=item.get("session"),
                tenant=item.get("tenant"),
                priority=item.get("priority"),
                speculative=item.get("speculative"), meta=meta)
        return self.submit(item)

    # -- routing ------------------------------------------------------

    def _choosable(self) -> list:
        return [r for r in self._replicas
                if r.alive and r.name not in self._updating]

    def _choose(self, req: _GwRequest):
        with self._lock:
            cands = self._choosable()
            if not cands:
                return None
            fresh = [r for r in cands if r.name not in req.tried]
            cands = fresh or cands  # all tried: go around again
            if not req.spec.get("handoff"):
                # a paged replica with ZERO free pages cannot admit a
                # fresh prefill without parking or shedding it — skip
                # page-exhausted replicas for NEW admissions under
                # every policy.  Handoff dispatches are exempt: the
                # disaggregated router already page-checked its decode
                # target, and excluding it here would unstick the
                # request from the replica its KV just shipped to.
                # All-exhausted falls through unchanged (the engine's
                # own parking/shedding beats a gateway-level drop).
                roomy = [r for r in cands
                         if _free_pages(r) != 0]
                cands = roomy or cands
            if self.policy == "least_loaded":
                # ties on load break on paged headroom (more free KV
                # pages first, so paged replicas absorb the burst);
                # envelope replicas report None and sort as 0 —
                # between queue depth and an exhausted paged pool
                def _key(r):
                    fp = _free_pages(r)
                    return (r.load(), 0 if fp is None else -fp,
                            r.name)
                return min(cands, key=_key)
            if (self.policy == "session"
                    and req.spec.get("session") is not None):
                cands = sorted(cands, key=lambda r: r.name)
                key = str(req.spec["session"]).encode()
                return cands[zlib.crc32(key) % len(cands)]
            if self.policy == "prefix":
                # deterministic over the SORTED candidate set, same
                # as session stickiness: equal prompt heads map to
                # the same replica as long as the replica set is
                # stable, and rehash consistently when it shrinks
                cands = sorted(cands, key=lambda r: r.name)
                key = req.spec["prompt"][:self.prefix_block].tobytes()
                return cands[zlib.crc32(key) % len(cands)]
            rep = cands[self._rr % len(cands)]
            self._rr += 1
            return rep

    def _dispatch(self, req: _GwRequest) -> None:
        rep = self._choose(req)
        if rep is None:
            # nothing routable: down-marked remotes may only have had
            # a transient wire fault — probe before burning an attempt
            for r in self._replicas:
                probe = getattr(r, "probe", None)
                if probe is not None and not r.alive:
                    with contextlib.suppress(Exception):
                        probe()
            rep = self._choose(req)
        if rep is None:
            # nothing routable NOW (all down or mid-update): burn one
            # attempt waiting rather than failing a survivable blip
            self._retry(req, None, "no_replica_available",
                        kind="failover")
            return
        req.tried.add(rep.name)
        telemetry.metrics().counter("gateway_requests_total",
                                    replica=rep.name,
                                    policy=self.policy).inc()
        try:
            rep.dispatch(req.spec,
                         lambda res: self._on_result(req, rep, res))
        except Exception as e:  # refused at the door (down/racing)
            self._on_result(req, rep, e)

    def _on_result(self, req: _GwRequest, rep, res) -> None:
        if req.future.ready():
            return  # a faster attempt already won
        kind = _classify(res)
        if self._closing or kind == "final":
            self._complete(req, res)
            return
        name = rep.name if rep is not None else "(none)"
        if kind == "failover":
            telemetry.metrics().counter("gateway_failovers_total",
                                        replica=name).inc()
            flight_recorder.record("failover", request_id=req.rid,
                                   replica=name, cause=_cause(res),
                                   attempt=req.attempts + 1)
        else:
            telemetry.metrics().counter("gateway_shed_retries_total",
                                        replica=name).inc()
        self._retry(req, rep, _cause(res), kind=kind)

    def _retry(self, req: _GwRequest, rep, cause: str, *,
               kind: str) -> None:
        req.attempts += 1
        if req.attempts > self.retries:
            telemetry.metrics().counter(
                "gateway_retries_exhausted_total").inc()
            self._complete(req, self._error_result(
                req, f"gateway_retries_exhausted: {cause}"))
            return
        self._retry_q.put((telemetry.now()
                           + self._backoff_delay(req.attempts),
                           next(self._seq), req))

    def _backoff_delay(self, attempt: int) -> float:
        delay = min(self.backoff_max,
                    self.backoff_base * 2 ** (attempt - 1))
        with self._lock:
            u = float(self._rng.random())
        return delay * (1.0 - self.jitter * u)

    def _retry_loop(self) -> None:
        while True:
            due, _, req = self._retry_q.get()
            if req is None:
                return
            wait = due - telemetry.now()
            if wait > 0:
                _sleep(wait)
            if self._closing:
                if not req.future.ready():
                    self._complete(req, self._error_result(
                        req, "gateway_closed"))
                continue
            self._dispatch(req)

    def _complete(self, req: _GwRequest, res) -> None:
        if isinstance(res, Exception):
            res = self._error_result(req, f"gateway: {res!r}")
        req.future.set(res)

    def _error_result(self, req: _GwRequest, error: str) -> dict:
        spec = req.spec
        return {**spec.get("meta", {}),
                "request_id": req.rid, "prompt": spec["prompt"],
                "tokens": np.zeros((0,), np.int32), "error": error,
                "attempts": req.attempts}

    # -- health -------------------------------------------------------

    def busy(self) -> bool:
        """True while any replica swap (``rolling_update`` /
        ``add_replica`` warm / ``remove_replica`` drain) is mid-flight
        — the ``Autoscaler(busy=gw.busy)`` guard, so scaling verbs
        never interleave with a live swap."""
        with self._lock:
            return bool(self._updating)

    def alive_replicas(self) -> int:
        """Routable capacity right now: replicas that are alive (a
        mid-update replica still counts — it comes back).  This is the
        ``Autoscaler(replica_count=...)`` hook and the drill's
        convergence observable."""
        return sum(1 for r in self._replicas if r.alive)

    def healthz(self) -> dict:
        """Aggregated verdict + per-replica verdicts.  ``critical``
        when no replica is alive; otherwise the worst alive replica's
        SLO state, floored at ``degraded`` while any replica is down
        or mid-update (capacity is reduced even if the survivors are
        healthy)."""
        rank = {"ok": 0, "degraded": 1, "critical": 2}
        replicas = {}
        worst, n_alive = "ok", 0
        with self._lock:
            updating = set(self._updating)
        for rep in self._replicas:
            h = rep.health()
            replicas[rep.name] = h
            if h.get("alive"):
                n_alive += 1
                s = h.get("state", "ok")
                if rank.get(s, 0) > rank[worst]:
                    worst = s
        if n_alive == 0:
            state = "critical"
        elif n_alive < len(self._replicas) or updating:
            state = worst if rank[worst] >= 1 else "degraded"
        else:
            state = worst
        telemetry.metrics().gauge("gateway_alive_replicas").set(
            n_alive)
        return {"state": state, "alive": n_alive,
                "total": len(self._replicas),
                "updating": sorted(updating), "replicas": replicas}

    # -- elastic membership -------------------------------------------

    def add_replica(self, replica, *, source=None,
                    quiesce_timeout: float = 60.0):
        """Admit a new replica without disturbing traffic: *register
        excluded* (routing never sees it yet, ``healthz`` shows it as
        updating) → *start* → *warm* (weights from ``source``, any
        form ``rolling_update`` accepts; default: a live peer, so the
        fleet stays uniform) → *admit*.  On any warm-up failure the
        replica is deregistered and the error re-raised — the serving
        set is never left with a cold member.  Returns the replica.
        """
        with self._lock:
            if self._closing:
                raise RuntimeError("gateway is closed")
            names = {r.name for r in self._replicas}
            if replica.name in names:
                raise ValueError(
                    f"replica name {replica.name!r} already "
                    f"registered")
            started = self._started
            self._updating.add(replica.name)
            self._replicas.append(replica)
        try:
            if started:
                replica.start()
            if source is None:
                with self._lock:
                    live = [r for r in self._replicas
                            if r.alive and r.name != replica.name]
                if live:
                    # a replica's variables() IS the full variables
                    # dict — _resolve_source passes it through
                    source = jax.device_get(dict(live[0].variables()))
            if source is not None and replica.alive:
                replica.swap(self._resolve_source(source))
        except Exception:
            with self._lock:
                self._replicas.remove(replica)
                self._updating.discard(replica.name)
            raise
        with self._lock:
            self._updating.discard(replica.name)
            total = len(self._replicas)
        flight_recorder.record("replica_add", replica=replica.name,
                               total=total)
        return replica

    def remove_replica(self, name: str, *,
                       quiesce_timeout: float = 60.0):
        """Drain a replica out of the serving set: *exclude from
        routing* → *quiesce* (its in-flight work completes; new
        requests already route elsewhere) → *deregister* → *stop* (a
        local ``EngineReplica``'s engine closes; a remote replica's
        server is left to its owner, same as ``stop()``).  Refuses to
        drain the last routable replica.  Returns the removed replica.
        """
        with self._lock:
            by_name = {r.name: r for r in self._replicas}
            rep = by_name.get(name)
            if rep is None:
                raise ValueError(f"no replica named {name!r}: "
                                 f"{sorted(by_name)}")
            routable = [r for r in self._replicas
                        if r.alive and r.name not in self._updating]
            if [r.name for r in routable] == [name]:
                raise ValueError(
                    f"refusing to drain {name!r}: it is the last "
                    f"routable replica")
            self._updating.add(name)
        try:
            if rep.alive:
                rep.quiesce(quiesce_timeout)
        finally:
            with self._lock:
                self._replicas.remove(rep)
                self._updating.discard(name)
                total = len(self._replicas)
        if isinstance(rep, EngineReplica):
            rep.stop()
        flight_recorder.record("replica_drain", replica=name,
                               total=total)
        return rep

    # -- rolling weight updates ---------------------------------------

    def _resolve_source(self, source) -> dict:
        """New weights from: a PS snapshot path, a live PS (``.center``
        — ``HostParameterServer`` / ``ShardedParameterServer``), a PS
        client (``.pull()``), a REPLICATED PS's address list (``[(host,
        port), ...]`` — each tried in order over the template-free
        ``b"V"`` center fetch, so the rollout sources from whichever
        replica currently serves; a fenced ex-primary refuses and the
        walk moves on), a ``{"params": ...}`` variables dict, or a raw
        parameter pytree."""
        import os

        if isinstance(source, (str, os.PathLike)):
            from distkeras_tpu import checkpoint

            params = checkpoint.ps_snapshot_center(source)
        elif (isinstance(source, (list, tuple)) and source
              and all(isinstance(a, (list, tuple)) and len(a) == 2
                      for a in source)):
            from distkeras_tpu.parallel import host_ps

            last_err: Exception | None = None
            for addr_host, addr_port in source:
                try:
                    obj = host_ps.fetch_center_obj(
                        str(addr_host), int(addr_port))
                    params = obj["center"]
                    break
                except (OSError, ValueError, KeyError) as e:
                    last_err = e
            else:
                raise ConnectionError(
                    f"no PS replica in {source!r} would serve the "
                    f"center") from last_err
        elif hasattr(source, "center"):
            params = source.center
        elif hasattr(source, "pull") and callable(source.pull):
            params = source.pull()
        elif isinstance(source, Mapping) and "params" in source:
            return dict(source)
        else:
            params = source
        return {"params": params}

    def rolling_update(self, source, *,
                       quiesce_timeout: float = 60.0,
                       health_check: Optional[Callable] = None
                       ) -> dict:
        """Drain + hot-swap one replica at a time while the rest keep
        serving; zero requests fail (draining excludes the replica
        from routing first, and the engine swap is rejected — not
        applied — on any structure mismatch).

        State machine per replica: *exclude from routing* → *quiesce*
        (drain its in-flight work) → *swap* (step-boundary install,
        no recompile) → *readmit* → *health re-check*.  If the check
        (default: the replica's own SLO verdict; pass
        ``health_check=lambda rep: ...`` to override) comes back
        ``critical``, every already-updated replica is rolled back to
        the pre-rollout weights and the rollout stops.  Dead replicas
        are skipped (they pick up current weights on restart).

        Returns ``{"updated": [...], "skipped": [...],
        "rolled_back": bool}``.
        """
        self.start()
        new_vars = self._resolve_source(source)
        check = health_check or (lambda rep: rep.health())
        report: dict = {"updated": [], "skipped": [],
                        "rolled_back": False}
        live = [r for r in self._replicas if r.alive]
        if not live:
            raise ReplicaDown("rolling_update: no replica alive")
        # the rollback image: the fleet is uniform between rollouts,
        # so any live replica's weights are THE previous version
        old_vars = jax.device_get(dict(live[0].variables()))
        with telemetry.span("rolling_update",
                            replicas=len(self._replicas)):
            for rep in self._replicas:
                if not rep.alive:
                    report["skipped"].append(rep.name)
                    continue
                self._swap_one(rep, new_vars, quiesce_timeout)
                verdict = check(rep)
                if verdict.get("state") == "critical":
                    self._rollback(report["updated"] + [rep.name],
                                   old_vars, quiesce_timeout)
                    report["rolled_back"] = True
                    report["verdict"] = verdict
                    return report
                report["updated"].append(rep.name)
        return report

    def _swap_one(self, rep, variables: Mapping,
                  quiesce_timeout: float) -> None:
        with telemetry.span("weight_swap", replica=rep.name):
            with self._lock:
                self._updating.add(rep.name)
            try:
                rep.quiesce(quiesce_timeout)
                rep.swap(variables)
            finally:
                with self._lock:
                    self._updating.discard(rep.name)
        telemetry.metrics().counter("gateway_weight_swaps_total",
                                    replica=rep.name).inc()
        flight_recorder.record("weight_swap", replica=rep.name)

    def _rollback(self, names: list, old_vars: Mapping,
                  quiesce_timeout: float) -> None:
        telemetry.metrics().counter("gateway_rollbacks_total").inc()
        flight_recorder.record("rollback", replicas=list(names))
        flight_recorder.flush()
        by_name = {r.name: r for r in self._replicas}
        with telemetry.span("rollback", replicas=len(names)):
            for name in names:
                rep = by_name[name]
                if rep.alive:
                    self._swap_one(rep, old_vars, quiesce_timeout)


# ---------------------------------------------------------------------
# disaggregated prefill/decode
# ---------------------------------------------------------------------


class PrefillDecodeRouter:
    """Two-stage disaggregated serving (the DistServe / Splitwise
    split): a PREFILL pool computes prompt KV, a DECODE pool owns
    token generation, and finished KV page blocks ship between them
    over the prefix-store interchange (``DecodeEngine.export_prefix``
    → wire scope ``"kv"`` → ``import_prefix``).

    Why: on a monolithic replica a long-prompt flood interleaves
    prefill programs with every live slot's decode steps, so INTER-
    TOKEN latency degrades fleet-wide.  Here the flood queues at the
    prefill pool — ``max_inflight_handoffs`` bounds prefill+export
    work in flight, the back-pressure valve — while decode replicas
    keep their step cadence.

    Request lifecycle:

    * a SHORT prompt (under one whole ``block_size`` block — nothing
      exportable) routes straight to the decode pool;
    * a LONG prompt runs the pipeline: the least-loaded prefill
      replica generates ONE token (its donation path warms the
      prefill-side prefix store), ``kv_export`` pulls the prompt's
      blocks, then the router picks a decode replica with page
      headroom (``free_pages() >= `` the request's worst-case page
      need; envelope replicas always qualify), probes the target's
      LOCAL store first (``kv_probe`` — the cluster-tier rung: ship
      only when the decode side doesn't already hold the blocks),
      ``kv_import``s the set (``serving_kv_pages_shipped_total``
      counts shipped blocks), and dispatches the real request with
      ``handoff=True``.  Decode-side admission takes the ordinary
      prefix-hit path, so tokens are byte-identical to a monolithic
      engine by construction.
    * a dead prefill pool degrades gracefully: the request falls
      through to the decode pool and recomputes its prefill there.

    Failure discipline mirrors ``ServingGateway``: seeded full-jitter
    backoff, ``retries`` extra attempts per stage, first-completion-
    wins futures (exactly-once delivery), and a decode replica dying
    mid-handoff requeues the request onto a survivor — counted by
    ``serving_handoff_requeue_total`` plus a ``handoff_requeue``
    flight event (the seeded chaos test pins exactly-once delivery
    under the kill).
    """

    def __init__(self, prefill: Iterable, decode: Iterable, *,
                 block_size: int, max_inflight_handoffs: int = 4,
                 retries: int = 3, backoff_base: float = 0.02,
                 backoff_max: float = 0.5, jitter: float = 0.5,
                 seed: int = 0, deadline: Optional[float] = None):
        self.prefill = list(prefill)
        self.decode = list(decode)
        if not self.prefill or not self.decode:
            raise ValueError(
                "PrefillDecodeRouter needs >= 1 replica per pool")
        names = [r.name for r in (*self.prefill, *self.decode)]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique: {names}")
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1; got {block_size}")
        if max_inflight_handoffs < 1:
            raise ValueError(f"max_inflight_handoffs must be >= 1; "
                             f"got {max_inflight_handoffs}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0; got {retries}")
        if not 0.0 <= jitter <= 1.0:
            raise ValueError(f"jitter={jitter} outside [0, 1]")
        # align block_size with the engines' page_size/prefill_align:
        # it sizes both the short-prompt cutoff and the page-headroom
        # requirement
        self.block_size = int(block_size)
        self.retries = int(retries)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.jitter = float(jitter)
        self.deadline = deadline
        self._rng = np.random.default_rng(seed)
        self._lock = racecheck.lock("gateway.pd_router")
        self._requests: dict[Any, tuple] = {}  # rid -> (spec, future)
        self._n_auto = itertools.count()
        self._handoffs = threading.Semaphore(
            int(max_inflight_handoffs))
        self._closing = False  # guarded-by: _lock
        self._started = False  # guarded-by: _lock

    # -- lifecycle ----------------------------------------------------

    def start(self) -> "PrefillDecodeRouter":
        with self._lock:
            if self._started:
                return self
            self._started = True
        for rep in (*self.prefill, *self.decode):
            rep.start()
        # pre-touch: the handoff counters must exist (at zero) in
        # every snapshot obs_report reads, handoffs or none
        m = telemetry.metrics()
        m.counter("serving_kv_pages_shipped_total").inc(0)
        m.counter("serving_handoff_requeue_total").inc(0)
        return self

    def stop(self) -> None:
        with self._lock:
            if self._closing:
                return
            self._closing = True
        for rep in (*self.prefill, *self.decode):
            if isinstance(rep, EngineReplica):
                rep.stop()
        with self._lock:
            reqs = list(self._requests.items())
        for rid, (spec, fut) in reqs:
            if not fut.ready():
                fut.set(self._error_result(rid, spec,
                                           "gateway_closed"))

    def __enter__(self) -> "PrefillDecodeRouter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- submission ---------------------------------------------------

    def submit(self, prompt, *,
               max_new_tokens: Optional[int] = None, eos_id=_UNSET,
               request_id=None, deadline=_UNSET,
               meta: Optional[Mapping] = None, tenant=None,
               priority: Optional[int] = None):
        """Queue one request through the two-stage pipeline; returns
        its id.  Same result contract as ``ServingGateway.submit``."""
        self.start()
        spec: dict = {"prompt": np.asarray(prompt, np.int32)}
        if max_new_tokens is not None:
            spec["max_new_tokens"] = int(max_new_tokens)
        if eos_id is not _UNSET:
            spec["eos_id"] = eos_id
        dl = self.deadline if deadline is _UNSET else deadline
        if dl is not None:
            spec["deadline"] = float(dl)
        if meta:
            spec["meta"] = dict(meta)
        if tenant is not None:
            spec["tenant"] = tenant
        if priority is not None:
            spec["priority"] = int(priority)
        with self._lock:
            if self._closing:
                raise RuntimeError("router is closed")
            if request_id is None:
                rid = f"pd-{next(self._n_auto)}"
                while rid in self._requests:
                    rid = f"pd-{next(self._n_auto)}"
            else:
                rid = request_id
                if rid in self._requests:
                    raise ValueError(
                        f"request_id {rid!r} is already in flight")
            spec["request_id"] = rid
            fut = _Future()
            self._requests[rid] = (spec, fut)
        threading.Thread(target=self._run_one, args=(rid, spec, fut),
                         daemon=True,
                         name=f"dkt-pd-{rid}").start()
        return rid

    def result(self, request_id,
               timeout: Optional[float] = None) -> dict:
        """Block for (and consume) one request's result."""
        with self._lock:
            ent = self._requests.get(request_id)
        if ent is None:
            raise KeyError(f"unknown request_id {request_id!r}")
        res = ent[1].wait(timeout)
        with self._lock:
            self._requests.pop(request_id, None)
        return res

    def try_result(self, request_id):
        """Non-blocking ``result`` (``None``: still in flight)."""
        with self._lock:
            ent = self._requests.get(request_id)
            if ent is None:
                raise KeyError(f"unknown request_id {request_id!r}")
            if not ent[1].ready():
                return None
            self._requests.pop(request_id, None)
        return ent[1].wait(0)

    def run(self, requests: Iterable, *, ordered: bool = True
            ) -> Iterator[dict]:
        """Serve an iterable to completion — one result per item,
        same item forms as ``ServingGateway.run`` (minus ``session``/
        ``speculative``, which have no disaggregated meaning yet)."""
        rids = [self._submit_item(item) for item in requests]
        if ordered:
            for rid in rids:
                yield self.result(rid)
            return
        pending = set(rids)
        while pending:
            done = [rid for rid in pending
                    if self._requests[rid][1].ready()]
            for rid in done:
                pending.discard(rid)
                yield self.result(rid)
            if not done:
                _sleep(0.002)

    def _submit_item(self, item):
        if isinstance(item, Mapping):
            meta = {k: v for k, v in item.items()
                    if k not in ("prompt", "max_new_tokens", "eos_id",
                                 "deadline", "tenant", "priority")}
            return self.submit(
                item["prompt"],
                max_new_tokens=item.get("max_new_tokens"),
                eos_id=item.get("eos_id", _UNSET),
                deadline=item.get("deadline", _UNSET),
                tenant=item.get("tenant"),
                priority=item.get("priority"), meta=meta)
        return self.submit(item)

    # -- the pipeline -------------------------------------------------

    def _run_one(self, rid, spec: dict, fut: _Future) -> None:
        try:
            prompt = spec["prompt"]
            export = None
            if len(prompt) // self.block_size > 0:
                with self._handoffs:  # back-pressure: floods wait HERE
                    export = self._prefill_stage(rid, spec, fut)
                if fut.ready():
                    return
            self._decode_stage(rid, spec, fut, export)
        except Exception as e:  # never leak a waiter
            fut.set(self._error_result(rid, spec, f"router: {e!r}"))

    def _prefill_stage(self, rid, spec: dict, fut: _Future):
        """Prefill the prompt on the prefill pool and pull its KV
        blocks.  Best-effort by design: every failure path returns
        ``None`` and the decode stage recomputes — degraded latency,
        never a lost request."""
        prompt = spec["prompt"]
        rep = None
        for attempt in range(self.retries + 1):
            if self._closing or fut.ready():
                return None
            rep = self._pick(self.prefill)
            if rep is None:
                self._backoff(attempt + 1)
                continue
            pspec = {"prompt": prompt, "max_new_tokens": 1,
                     "request_id": f"{rid}#p{attempt}"}
            if "deadline" in spec:
                pspec["deadline"] = spec["deadline"]
            att = _Future()
            telemetry.metrics().counter(
                "gateway_requests_total", replica=rep.name,
                policy="prefill_decode").inc()
            try:
                with telemetry.span("prefill_stage", replica=rep.name,
                                    request_id=str(rid)):
                    rep.dispatch(pspec, att.set)
                    res = att.wait()
            except Exception as e:
                res = e
            if (_classify(res) == "final"
                    and not isinstance(res, Exception)
                    and res.get("error") is None):
                break
            self._backoff(attempt + 1)
        else:
            return None  # pool down/erroring: recompute on decode
        try:
            return rep.kv_export(prompt)
        except Exception:
            return None  # severed mid-export: recompute on decode

    def _decode_stage(self, rid, spec: dict, fut: _Future,
                      export) -> None:
        m = telemetry.metrics()
        need = paging.pages_for(
            len(spec["prompt"]) + int(spec.get("max_new_tokens", 1)),
            self.block_size)
        dspec = dict(spec)
        dspec["handoff"] = True
        last = None
        for attempt in range(self.retries + 1):
            if self._closing or fut.ready():
                return
            rep = self._pick(self.decode, need_pages=need)
            if rep is None:
                last = ReplicaDown("no decode replica available")
                self._backoff(attempt + 1)
                continue
            if export is not None and export["n_blocks"]:
                try:
                    # cluster-tier rung: the decode replica's LOCAL
                    # store first; ship only when it is missing blocks
                    if (rep.kv_probe(export["prompt"])
                            < export["n_blocks"]):
                        shipped = rep.kv_import(export)
                        m.counter(
                            "serving_kv_pages_shipped_total").inc(
                                shipped)
                except Exception as e:  # died mid-handoff: requeue
                    last = e
                    self._requeue(rid, rep, e, attempt)
                    continue
            att = _Future()
            m.counter("gateway_requests_total", replica=rep.name,
                      policy="prefill_decode").inc()
            try:
                rep.dispatch(dspec, att.set)
                res = att.wait()
            except Exception as e:
                res = e
            if _classify(res) == "final":
                self._complete(rid, spec, fut, res)
                return
            last = res
            self._requeue(rid, rep, res, attempt)
        self._complete(rid, spec, fut, self._error_result(
            rid, spec, f"handoff_retries_exhausted: {_cause(last)}"))

    def _pick(self, pool: list, need_pages: Optional[int] = None):
        """Least-loaded alive replica (ties: more free pages, then
        name).  With ``need_pages``, paged replicas short of that
        headroom are skipped — envelope replicas (``free_pages() is
        None``) always qualify — falling back to the full candidate
        set when every paged replica is short (the engine's own
        parking/shedding then applies back-pressure)."""
        cands = [r for r in pool if r.alive]
        if not cands:
            # down-marked remotes may only have had a transient wire
            # fault (chaos reset, server restart) — probe before
            # writing the whole pool off, as ServingGateway does
            for r in pool:
                probe = getattr(r, "probe", None)
                if probe is not None and not r.alive:
                    with contextlib.suppress(Exception):
                        probe()
            cands = [r for r in pool if r.alive]
        if not cands:
            return None
        if need_pages is not None:
            roomy = [r for r in cands
                     if (_free_pages(r) is None
                         or _free_pages(r) >= need_pages)]
            cands = roomy or cands
        def _key(r):
            fp = _free_pages(r)
            return (r.load(), 0 if fp is None else -fp, r.name)
        return min(cands, key=_key)

    def _requeue(self, rid, rep, cause, attempt: int) -> None:
        telemetry.metrics().counter(
            "serving_handoff_requeue_total").inc()
        telemetry.metrics().counter("gateway_failovers_total",
                                    replica=rep.name).inc()
        flight_recorder.record("handoff_requeue", request_id=rid,
                               replica=rep.name, cause=_cause(cause),
                               attempt=attempt + 1)
        self._backoff(attempt + 1)

    def _backoff(self, attempt: int) -> None:
        delay = min(self.backoff_max,
                    self.backoff_base * 2 ** (attempt - 1))
        with self._lock:
            u = float(self._rng.random())
        _sleep(delay * (1.0 - self.jitter * u))

    def _complete(self, rid, spec: dict, fut: _Future, res) -> None:
        if isinstance(res, Exception):
            res = self._error_result(rid, spec, f"router: {res!r}")
        fut.set(res)

    def _error_result(self, rid, spec: dict, error: str) -> dict:
        return {**spec.get("meta", {}),
                "request_id": rid, "prompt": spec["prompt"],
                "tokens": np.zeros((0,), np.int32), "error": error}

    # -- health -------------------------------------------------------

    def healthz(self) -> dict:
        """Per-pool replica verdicts + the aggregate state:
        ``critical`` with no decode replica alive (nothing can finish
        a request), ``degraded`` with the prefill pool down or any
        replica dead (capacity or the disaggregation benefit is
        reduced), else the worst alive replica's SLO state."""
        rank = {"ok": 0, "degraded": 1, "critical": 2}
        pools, worst = {}, "ok"
        alive = {"prefill": 0, "decode": 0}
        for pool_name, pool in (("prefill", self.prefill),
                                ("decode", self.decode)):
            pools[pool_name] = {}
            for rep in pool:
                h = rep.health()
                pools[pool_name][rep.name] = h
                if h.get("alive"):
                    alive[pool_name] += 1
                    s = h.get("state", "ok")
                    if rank.get(s, 0) > rank[worst]:
                        worst = s
        if alive["decode"] == 0:
            state = "critical"
        elif (alive["prefill"] == 0
              or alive["prefill"] < len(self.prefill)
              or alive["decode"] < len(self.decode)):
            state = worst if rank[worst] >= 1 else "degraded"
        else:
            state = worst
        return {"state": state, "alive": alive,
                "pools": pools}


def _sleep(seconds: float) -> None:
    if seconds > 0:
        import time

        time.sleep(seconds)
