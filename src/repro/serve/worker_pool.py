"""WorkerPool: N ServingWorker processes behind socket endpoints.

Scale-out for the fuse-to-serve path (docs/serving.md): each pool member
is its OWN process running a ``ServingWorker`` — its own follower
(polling ``repository.json`` cross-process), its own engine, its own
namespaced ``serving_state-<id>.json`` — fronted by a tiny
newline-delimited-JSON TCP protocol on a loopback port.  The parent
``WorkerPool`` spawns the children (``python -m repro.serve.worker_pool``
is the child entry point), discovers each child's port from its state
file, and hands out ``SocketEndpoint``s that plug into
``repro.serve.router.Router``.

Isolation is the point: a worker kill -9'd mid-swap takes down one
process — its state file goes stale, the router marks it dead on the
transport error and re-routes the in-flight-failed request exactly once,
and every other worker keeps serving.  The repository's durability
discipline (base npz durable before ``repository.json`` names it) means
a restarted worker can only ever adopt a published, uncorrupted base.

Protocol (one JSON object per line, request/response):

    {"op": "generate", "prompt": [..], "max_new_tokens": 4}
      -> {"ok": true, "tokens": [..], "iteration": 3, "steps": 4,
          "batch_size": 2, "latency_s": 0.01}
      -> {"ok": false, "rejected": "queue_full"}     (worker shedding)
      -> {"ok": false, "error": "..."}               (worker error)
    {"op": "ping"}  -> {"ok": true, "iteration": 3}

The child's ``--engine value`` selects a closed-form fake engine
(generation returns the served tree's scalar ``w`` value, so a token
mismatch IS a version tear) — the cross-process pinning and kill-matrix
tests use it to verify exact served weights without paying a real
model; ``--engine real`` (the default) builds the ``Engine`` from a
reduced arch config.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import socketserver
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import io as ckpt
from repro.serve.cold_service import serving_state_filename
from repro.serve.router import EndpointDied, Router
from repro.serve.scheduler import RequestRejected

__all__ = ["SocketEndpoint", "WorkerPool"]

_CONNECT_TIMEOUT_S = 5.0


class SocketEndpoint:
    """A pool child as a routable endpoint: health from its namespaced
    state file, generation over the loopback socket.  Transport failures
    (refused, reset, EOF, timeout) raise ``EndpointDied``; an alive
    worker's explicit shed raises ``RequestRejected`` — the router
    treats the two differently."""

    def __init__(self, root: str, worker_id: str, *,
                 request_timeout_s: float = 120.0):
        self.root = root
        self.id = str(worker_id)
        self.request_timeout_s = float(request_timeout_s)
        self._port: Optional[int] = None

    def health(self) -> Optional[Dict[str, Any]]:
        try:
            return ckpt.load_json(
                os.path.join(self.root, serving_state_filename(self.id)))
        except (FileNotFoundError, ValueError):
            return None

    def _resolve_port(self) -> int:
        # re-read on every miss: a restarted worker re-registers a NEW
        # port through the same state file
        h = self.health()
        if not h or not h.get("port"):
            raise EndpointDied(f"{self.id}: no registered port")
        self._port = int(h["port"])
        return self._port

    def _call(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        port = self._port or self._resolve_port()
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=_CONNECT_TIMEOUT_S) as sk:
                sk.settimeout(self.request_timeout_s)
                sk.sendall((json.dumps(payload) + "\n").encode())
                buf = b""
                while not buf.endswith(b"\n"):
                    chunk = sk.recv(65536)
                    if not chunk:
                        raise EndpointDied(f"{self.id}: connection closed "
                                           "mid-request")
                    buf += chunk
        except (OSError, socket.timeout) as err:
            self._port = None   # stale port: re-resolve next time
            raise EndpointDied(f"{self.id}: {err}") from err
        return json.loads(buf.decode())

    def ping(self) -> Dict[str, Any]:
        return self._call({"op": "ping"})

    def generate(self, prompt: np.ndarray, *, max_new_tokens: int,
                 deadline_s: Optional[float] = None) -> Dict[str, Any]:
        out = self._call({
            "op": "generate",
            "prompt": np.asarray(prompt).tolist(),
            "max_new_tokens": int(max_new_tokens),
            "deadline_s": deadline_s,
        })
        if not out.get("ok"):
            if out.get("rejected"):
                raise RequestRejected(out["rejected"], self.id)
            raise EndpointDied(f"{self.id}: {out.get('error')}")
        out["tokens"] = np.asarray(out["tokens"])
        return out


class WorkerPool:
    """Spawn and manage N serving-worker processes under one root.

    ``child_env`` maps worker id -> extra environment for that child —
    the kill-matrix tests arm ``REPRO_CRASH_POINT`` on one member so it
    dies at an exact swap seam while its peers keep serving.  Children
    inherit the parent environment minus ``XLA_FLAGS`` (a forced
    fake-device mesh belongs to the fusion daemon, not the CPU serving
    children).

    The children are CPU serving processes that each import JAX.  On a
    TPU host the parent (the fusion daemon) already holds the chip, and a
    child that reaches for it fails or hangs, so ``start`` refuses when
    the parent's backend is ``tpu``."""

    def __init__(self, root: str, n_workers: int, *, arch: str = None,
                 engine: str = "real", max_len: int = 64,
                 poll: float = 0.02, batch: bool = False,
                 queue_depth: int = 64, max_batch: int = 8,
                 batch_wait_s: float = 0.002, family: Optional[str] = None,
                 warm: Optional[tuple] = None,
                 env: Optional[Dict[str, str]] = None,
                 child_env: Optional[Dict[str, Dict[str, str]]] = None):
        if engine == "real" and not arch:
            raise ValueError("engine='real' needs an arch name")
        self.root = str(root)
        self.worker_ids = [f"w{i}" for i in range(int(n_workers))]
        self.arch, self.engine = arch, engine
        self.max_len, self.poll = int(max_len), float(poll)
        self.batch = bool(batch)
        self.queue_depth, self.max_batch = int(queue_depth), int(max_batch)
        self.batch_wait_s = float(batch_wait_s)
        self.family = family
        # (prompt_len, max_new_tokens) to pre-compile before admitting
        # traffic: the child warms its engine's jit cache across the
        # batch buckets at this shape, so a cold worker doesn't stall
        # its first clients for seconds per bucket
        self.warm = warm
        self.env = dict(env or {})          # applied to every child
        self.child_env = dict(child_env or {})   # per-worker overrides
        self._procs: Dict[str, subprocess.Popen] = {}
        self._logs: Dict[str, Any] = {}
        self.endpoints: List[SocketEndpoint] = []

    def _spawn(self, wid: str) -> subprocess.Popen:
        # repro is a namespace package (no __init__.py): derive src/ from
        # its search path, not __file__ (which is None)
        import repro
        src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env.update(self.env)
        env.update(self.child_env.get(wid, {}))
        cmd = [sys.executable, "-m", "repro.serve.worker_pool",
               "--root", self.root, "--worker-id", wid,
               "--engine", self.engine, "--max-len", str(self.max_len),
               "--poll", str(self.poll),
               "--queue-depth", str(self.queue_depth),
               "--max-batch", str(self.max_batch),
               "--batch-wait", str(self.batch_wait_s)]
        if self.arch:
            cmd += ["--arch", self.arch]
        if self.warm:
            cmd += ["--warm", f"{self.warm[0]},{self.warm[1]}"]
        if self.batch:
            cmd += ["--batch"]
        if self.family:
            cmd += ["--family", self.family]
        log = open(os.path.join(self.root, f"worker-{wid}.log"), "ab")
        self._logs[wid] = log
        return subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)

    def start(self, *, timeout: float = 60.0) -> "WorkerPool":
        """Spawn every child and wait until each registered its port."""
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "WorkerPool spawns serving children that import JAX, and "
                "this process holds the TPU: a child would fail or hang "
                "reaching for it.  Serve in-process on a TPU host.")
        for wid in self.worker_ids:
            self._procs[wid] = self._spawn(wid)
        self.endpoints = [SocketEndpoint(self.root, wid)
                          for wid in self.worker_ids]
        deadline = time.monotonic() + timeout
        for ep in self.endpoints:
            while True:
                h = ep.health()
                if h and h.get("port"):
                    break
                proc = self._procs[ep.id]
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"pool child {ep.id} exited with "
                        f"{proc.returncode} before registering (see "
                        f"worker-{ep.id}.log)")
                if time.monotonic() > deadline:
                    raise TimeoutError(f"pool child {ep.id} never "
                                       "registered a port")
                time.sleep(0.02)
        return self

    def wait_ready(self, *, iteration: Optional[int] = None,
                   timeout: float = 60.0) -> None:
        """Block until every LIVE worker adopted a base (optionally a
        specific iteration).  Workers that already died (e.g. an armed
        crash point fired) are skipped — the router's job is exactly to
        survive them."""
        deadline = time.monotonic() + timeout
        for ep in self.endpoints:
            while True:
                proc = self._procs.get(ep.id)
                if proc is not None and proc.poll() is not None:
                    break
                h = ep.health()
                it = None if h is None else h.get("iteration")
                if it is not None and (iteration is None
                                       or it == iteration):
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"worker {ep.id} never adopted "
                        f"{'a base' if iteration is None else iteration}")
                time.sleep(0.02)

    def router(self, **kw) -> Router:
        return Router(self.endpoints, **kw)

    def kill(self, wid: str) -> None:
        """kill -9 one member (the fault the router must survive)."""
        self._procs[wid].kill()
        self._procs[wid].wait()

    def alive(self) -> List[str]:
        return [wid for wid, p in self._procs.items() if p.poll() is None]

    def stop(self, *, timeout: float = 30.0) -> Dict[str, int]:
        """SIGTERM every live child (clean shutdown: final state persist)
        and reap; returns exit codes."""
        codes: Dict[str, int] = {}
        for wid, proc in self._procs.items():
            if proc.poll() is None:
                proc.terminate()
        for wid, proc in self._procs.items():
            try:
                codes[wid] = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                codes[wid] = proc.wait()
        for log in self._logs.values():
            log.close()
        self._logs.clear()
        return codes

    def states(self) -> Dict[str, Optional[Dict[str, Any]]]:
        return {ep.id: ep.health() for ep in self.endpoints}


# ---------------------------------------------------------------------------
# child entry point
# ---------------------------------------------------------------------------


class _ValueEngine:
    """Closed-form fake engine (mirrors the hot_swap test fake): tokens
    are the served tree's scalar ``w`` value — any batch shape, so the
    scheduler path is exercised too."""

    def __init__(self, cfg, params, max_len):
        self.params = params

    def generate(self, prompts, *, max_new_tokens=16, params=None):
        import types
        p = self.params if params is None else params
        val = float(np.asarray(p["w"]).reshape(-1)[0])
        toks = np.full((prompts.shape[0], prompts.shape[1] + max_new_tokens),
                       val, np.float32)
        return types.SimpleNamespace(tokens=toks,
                                     prompt_len=int(prompts.shape[1]),
                                     steps=int(max_new_tokens))


def _child_main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="serving-pool worker process (docs/serving.md)")
    p.add_argument("--root", required=True)
    p.add_argument("--worker-id", required=True)
    p.add_argument("--arch", default=None)
    p.add_argument("--engine", choices=("real", "value"), default="real")
    p.add_argument("--family", default=None)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--poll", type=float, default=0.02)
    p.add_argument("--batch", action="store_true")
    p.add_argument("--queue-depth", type=int, default=64)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--batch-wait", type=float, default=0.002)
    p.add_argument("--warm", default=None, metavar="T,N",
                   help="pre-compile generate for prompt_len T / "
                        "max_new N across the batch buckets before "
                        "serving (first adoption blocks until warm)")
    args = p.parse_args(argv)

    from repro.serve.hot_swap import ServingWorker
    if args.engine == "value":
        cfg, factory = None, _ValueEngine
    else:
        from repro.configs import get_config, reduce_config
        cfg, factory = reduce_config(get_config(args.arch)), None
    worker = ServingWorker(
        cfg, args.root, family=args.family, max_len=args.max_len,
        name=args.worker_id, worker_id=args.worker_id,
        engine_factory=factory, batch_requests=args.batch,
        queue_depth=args.queue_depth, max_batch=args.max_batch,
        batch_wait_s=args.batch_wait)

    if args.warm:
        # adopt the first published base and pre-compile the bucketed
        # generate shapes NOW — a cold jit compile costs seconds per
        # shape, which must not stall the first clients (the parent's
        # start() waits on port registration, which happens after this)
        from repro.serve.scheduler import BATCH_BUCKETS
        warm_t, warm_n = (int(x) for x in args.warm.split(","))
        deadline = time.monotonic() + 120.0
        while worker.current_iteration is None:
            if worker.poll_once():
                break
            if time.monotonic() > deadline:
                break   # nothing published yet: serve cold
            time.sleep(0.05)
        if worker._engine is not None:
            dummy = np.full((1, warm_t), 2, np.int32)
            shapes = [b for b in BATCH_BUCKETS
                      if b <= args.max_batch] if args.batch else [1]
            for b in shapes:
                worker._engine.generate(np.repeat(dummy, b, axis=0),
                                        max_new_tokens=warm_n)

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            for line in self.rfile:
                try:
                    req = json.loads(line.decode())
                    out = self._dispatch(req)
                except Exception as err:  # noqa: BLE001 - report, don't die
                    out = {"ok": False,
                           "error": f"{type(err).__name__}: {err}"}
                self.wfile.write((json.dumps(out) + "\n").encode())
                self.wfile.flush()

        def _dispatch(self, req):
            if req.get("op") == "ping":
                return {"ok": True, "iteration": worker.current_iteration}
            if req.get("op") != "generate":
                return {"ok": False, "error": f"unknown op {req.get('op')}"}
            prompt = np.asarray(req["prompt"])[None, :]
            try:
                res = worker.generate(
                    prompt, max_new_tokens=int(req["max_new_tokens"]),
                    deadline_s=req.get("deadline_s"))
            except RequestRejected as err:
                return {"ok": False, "rejected": err.reason}
            return {"ok": True, "tokens": np.asarray(res.tokens)[0].tolist(),
                    "iteration": res.iteration, "steps": res.steps,
                    "batch_size": res.batch_size,
                    "latency_s": res.latency_s}

    class Server(socketserver.ThreadingTCPServer):
        daemon_threads = True
        allow_reuse_address = True

    srv = Server(("127.0.0.1", 0), Handler)
    port = srv.server_address[1]
    worker.extra_state["port"] = port
    worker.extra_state["worker_id"] = args.worker_id
    # register the port BEFORE the watch thread starts: the parent pool
    # blocks on this state file
    worker._persist_state()
    worker.start(interval=args.poll)

    def _term(signum, frame):
        threading.Thread(target=srv.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    print(f"[pool-worker] {args.worker_id} serving on 127.0.0.1:{port} "
          f"(engine={args.engine}, batch={args.batch})", flush=True)
    try:
        srv.serve_forever(poll_interval=0.1)
    finally:
        srv.server_close()
        st = worker.stop()
        print(f"[pool-worker] {args.worker_id} stopped at iteration "
              f"{st['iteration']}: {st['requests_total']} requests, "
              f"{st['swaps_total']} swaps", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
