#!/usr/bin/env python
"""Real multi-process test mesh: launcher + worker helpers (ISSUE 13).

Everything multi-host in this repo used to be "validated on a virtual
8-device single-process CPU mesh" — which cannot exercise consensus,
per-host faults, or host-local threads. This module launches N ACTUAL
processes, each bringing up ``jax.distributed.initialize`` on the CPU
backend (the coordination service rendezvous the PADDLE_* env protocol
already carries), with **chaos hooks that kill or hang exactly ONE
process at a named point** — so every kill-one claim in the multihost
test tree (tests/multihost/) runs against a real dead process, not a
simulated flag.

Launcher (driver side, e.g. inside a pytest test)::

    import mp_mesh
    res = mp_mesh.launch(2, "tests/multihost/worker_x.py", [out_dir],
                         log_dir=log_dir,
                         chaos="kill:1:pre_vote",      # optional
                         expect_fail_ranks=(1,))
    assert res.ok, res.tail()

Elastic chaos driver (ISSUE 17) — run the mesh ASYNC, kill a member
and/or spawn a mid-run joiner from the test process, then wait::

    h = mp_mesh.launch_async(2, worker, [out_dir], log_dir=log_dir)
    ...                                  # watch the shared dir
    h.kill_rank(1)                       # a real SIGKILL corpse
    h.spawn_rank(2, world=3)             # joiner (init_env_only)
    assert h.wait(120).ok

Worker side (the launched script)::

    import mp_mesh                       # tools/ is put on sys.path
    rank, world = mp_mesh.init()         # jax.distributed.initialize
    mp_mesh.barrier("up")                # coordination-service barrier
    mp_mesh.chaos_point("pre_vote")      # dies/hangs HERE if selected
    ...
    mp_mesh.finish(ok_file)              # marker + deterministic exit

Known container truth (jax 0.4.37): the coordination service works
across real CPU processes (barriers + KV store), but COMPILED
multiprocess collectives are unimplemented on the CPU backend
("Multiprocess computations aren't implemented") — so the mesh's data
plane in tests is host-side (the consensus board, the handoff channel,
per-rank sinks), which is exactly the part multi-host serving needs to
prove. jax >= 0.5 adds CPU cross-process collectives; the harness is
ready for them (ROADMAP residue).

``finish()`` exits via ``os._exit`` after flushing: a killed peer makes
the coordination service's OWN teardown error/hang on the survivors'
interpreter exit (its heartbeat declares the job failed), and a chaos
test must distinguish "survivor logic passed" from "jax teardown
noticed the corpse". The ok-marker protocol + hard exit does that.

Two more measured mesh truths the chaos tests are built around:

- the coordination service's fatal-error poller ABORTS surviving
  processes once it detects a dead task, and its detection callback
  cannot be replaced on this jaxlib (std::bad_cast) — but detection is
  heartbeat-driven (default 10 s x 10 missing ~= 100 s), so survivors
  have a measured >= 12 s (tested) window to finish their work on
  DEFAULT settings. Keep chaos workers short; never tighten the jax
  heartbeats. The consensus board's own leases (seconds) provide the
  fast failure detection the tests assert on.
- rank 0 HOSTS the service: its abrupt exit kills every peer within
  grpc's socket-closure notice, not the heartbeat window. So chaos
  targets are ranks >= 1, and rank 0 exits LAST — ``finish_last()``
  encodes that (wait for the survivors' ok markers, then exit).
"""
from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: chaos env var: "<kind>:<rank>:<point>[:<seconds>]", kind kill|hang
CHAOS_ENV = "MPMESH_CHAOS"
KILL_EXIT = 137


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def can_spawn() -> bool:
    """Whether this host can run the mesh at all (the ``multihost``
    marker auto-skips when it can't): subprocess spawn + localhost
    sockets, and not explicitly disabled."""
    if os.environ.get("MPMESH_DISABLE"):
        return False
    try:
        _free_port()
        subprocess.run([sys.executable, "-c", "pass"], timeout=60,
                       check=True, capture_output=True)
        return True
    except Exception:
        return False


class MeshResult:
    """Per-rank exit codes + logs of one mesh run."""

    def __init__(self, returncodes: Dict[int, int], log_dir: str,
                 expect_fail_ranks: Sequence[int], timed_out: bool):
        self.returncodes = returncodes
        self.log_dir = log_dir
        self.expect_fail_ranks = tuple(expect_fail_ranks)
        self.timed_out = timed_out

    @property
    def ok(self) -> bool:
        if self.timed_out:
            return False
        for r, rc in self.returncodes.items():
            if r in self.expect_fail_ranks:
                if rc == 0:
                    return False      # the chaos target SURVIVED
            elif rc != 0:
                return False
        return True

    def log(self, rank: int) -> str:
        try:
            with open(os.path.join(self.log_dir,
                                   f"workerlog.{rank}")) as f:
                return f.read()
        except OSError:
            return ""

    def tail(self, n_chars: int = 2000) -> str:
        out = [f"timed_out={self.timed_out} rcs={self.returncodes}"]
        for r in sorted(self.returncodes):
            out.append(f"--- workerlog.{r} ---\n{self.log(r)[-n_chars:]}")
        return "\n".join(out)


def launch(nprocs: int, script: str, script_args: Sequence[str] = (),
           *, log_dir: str, timeout: float = 300.0,
           chaos: Optional[str] = None,
           expect_fail_ranks: Sequence[int] = (),
           host_devices: int = 1,
           env_extra: Optional[Dict[str, str]] = None) -> MeshResult:
    """Spawn ``nprocs`` real worker processes with the PADDLE_* env
    protocol (rank 0's endpoint is the jax coordinator) and watch them.

    Unlike ``distributed.launch`` (which tears the whole job down on
    the FIRST failure — the training-fleet contract), this watcher
    tolerates nonzero exits of ``expect_fail_ranks`` (the chaos
    targets) and lets the survivors run to completion: kill-one tests
    are about the survivors. Any OTHER rank failing, or the timeout
    expiring, terminates the mesh and fails the result."""
    if nprocs < 1:
        raise ValueError("nprocs must be >= 1")
    os.makedirs(log_dir, exist_ok=True)
    base = _free_port()
    endpoints = [f"127.0.0.1:{base + i}" for i in range(nprocs)]
    # distinct ports: bind checks only port 'base'; collisions in the
    # tail are rare but possible — probe each
    for i in range(1, nprocs):
        with socket.socket() as s:
            try:
                s.bind(("", base + i))
            except OSError:
                return launch(nprocs, script, script_args,
                              log_dir=log_dir, timeout=timeout,
                              chaos=chaos,
                              expect_fail_ranks=expect_fail_ranks,
                              host_devices=host_devices,
                              env_extra=env_extra)
    procs: List[subprocess.Popen] = []
    logs = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_COORDINATOR": endpoints[0],
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count="
                          + str(host_devices)).strip(),
        })
        if chaos:
            env[CHAOS_ENV] = chaos
        if env_extra:
            env.update(env_extra)
        out = open(os.path.join(log_dir, f"workerlog.{rank}"), "w")
        logs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, script] + [str(a) for a in script_args],
            env=env, stdout=out, stderr=subprocess.STDOUT, cwd=REPO))
    handle = MeshHandle(script, list(script_args), log_dir,
                        endpoints, list(expect_fail_ranks), chaos,
                        host_devices, env_extra)
    handle._procs = dict(enumerate(procs))
    handle._logs = logs
    return handle.wait(timeout)


class MeshHandle:
    """An ASYNC mesh (ISSUE 17): the workers run while the driver —
    the test process — interacts with them. This is what the elastic
    chaos legs need: spawn a JOINER process mid-run
    (``spawn_rank(rank, world)``), hard-kill a member
    (``kill_rank``), then ``wait()`` for the same verdict ``launch``
    returns. Joiner workers use ``init_env_only()`` + the shared
    board: jax's coordination service cannot rendezvous a process
    that wasn't in the original world, and the elastic control plane
    deliberately doesn't need it to."""

    def __init__(self, script: str, script_args: List[str],
                 log_dir: str, endpoints: List[str],
                 expect_fail_ranks: List[int], chaos: Optional[str],
                 host_devices: int,
                 env_extra: Optional[Dict[str, str]]):
        self.script = script
        self.script_args = script_args
        self.log_dir = log_dir
        self.endpoints = endpoints
        self.expect_fail_ranks = expect_fail_ranks
        self.chaos = chaos
        self.host_devices = host_devices
        self.env_extra = env_extra
        self._procs: Dict[int, subprocess.Popen] = {}
        self._logs: List = []

    def _worker_env(self, rank: int, world: int) -> Dict[str, str]:
        while len(self.endpoints) < world:
            self.endpoints.append(f"127.0.0.1:{_free_port()}")
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_CURRENT_ENDPOINT": self.endpoints[rank],
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS":
                ",".join(self.endpoints[:world]),
            "PADDLE_COORDINATOR": self.endpoints[0],
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", ""),
            "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count="
                          + str(self.host_devices)).strip(),
        })
        if self.chaos:
            env[CHAOS_ENV] = self.chaos
        if self.env_extra:
            env.update(self.env_extra)
        return env

    def spawn_rank(self, rank: int, world: int,
                   script_args: Optional[Sequence[str]] = None,
                   env_extra: Optional[Dict[str, str]] = None
                   ) -> subprocess.Popen:
        """Start one MORE worker process — the mid-run joiner. The
        joiner sees ``PADDLE_TRAINERS_NUM=world`` (its own view of
        the target world; existing members keep theirs — dynamic
        membership reconciles them on the board, which is the point
        being tested). Its exit code joins the ``wait()`` verdict."""
        if rank in self._procs:
            raise ValueError(f"rank {rank} already running")
        env = self._worker_env(rank, world)
        if env_extra:
            env.update(env_extra)
        out = open(os.path.join(self.log_dir,
                                f"workerlog.{rank}"), "w")
        self._logs.append(out)
        args = (self.script_args if script_args is None
                else list(script_args))
        p = subprocess.Popen(
            [sys.executable, self.script] + [str(a) for a in args],
            env=env, stdout=out, stderr=subprocess.STDOUT, cwd=REPO)
        self._procs[rank] = p
        return p

    def kill_rank(self, rank: int) -> None:
        """SIGKILL one member — the real corpse the elastic legs
        re-dispatch around (no cleanup, no goodbyes, like the OOM
        killer). The rank is auto-added to ``expect_fail_ranks``."""
        p = self._procs[rank]
        if p.poll() is None:
            p.kill()
        if rank not in self.expect_fail_ranks:
            self.expect_fail_ranks.append(rank)

    def poll_rank(self, rank: int) -> Optional[int]:
        return self._procs[rank].poll()

    def wait(self, timeout: float = 300.0) -> MeshResult:
        """Watch every spawned process (including late joiners) to
        completion — same tolerance contract as ``launch``."""
        rcs: Dict[int, int] = {}
        deadline = time.time() + timeout
        timed_out = False
        try:
            while len(rcs) < len(self._procs):
                if time.time() > deadline:
                    timed_out = True
                    break
                hard_fail = False
                for r, p in list(self._procs.items()):
                    if r in rcs:
                        continue
                    rc = p.poll()
                    if rc is not None:
                        rcs[r] = rc
                        if rc != 0 and \
                                r not in self.expect_fail_ranks:
                            hard_fail = True
                if hard_fail:
                    break
                time.sleep(0.05)
        finally:
            for r, p in self._procs.items():
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            kill_at = time.time() + 10
            for r, p in self._procs.items():
                while p.poll() is None and time.time() < kill_at:
                    time.sleep(0.1)
                if p.poll() is None:
                    p.kill()
                    p.wait()
                rcs.setdefault(r, p.returncode)
            for f in self._logs:
                f.close()
            self._logs = []
        return MeshResult(rcs, self.log_dir,
                          tuple(self.expect_fail_ranks), timed_out)


def launch_async(nprocs: int, script: str,
                 script_args: Sequence[str] = (), *, log_dir: str,
                 chaos: Optional[str] = None,
                 expect_fail_ranks: Sequence[int] = (),
                 host_devices: int = 1,
                 world: Optional[int] = None,
                 env_extra: Optional[Dict[str, str]] = None
                 ) -> MeshHandle:
    """Start ``nprocs`` workers and return WITHOUT waiting: the
    elastic chaos driver (ISSUE 17) — kill a rank mid-run, spawn a
    joiner, then ``handle.wait()``. ``world`` overrides the
    PADDLE_TRAINERS_NUM the initial ranks see (default ``nprocs``);
    the endpoint list grows as joiners spawn."""
    if nprocs < 1:
        raise ValueError("nprocs must be >= 1")
    os.makedirs(log_dir, exist_ok=True)
    endpoints = [f"127.0.0.1:{_free_port()}" for _ in range(nprocs)]
    handle = MeshHandle(script, list(script_args), log_dir,
                        endpoints, list(expect_fail_ranks), chaos,
                        host_devices, env_extra)
    for rank in range(nprocs):
        env = handle._worker_env(rank, world or nprocs)
        out = open(os.path.join(log_dir, f"workerlog.{rank}"), "w")
        handle._logs.append(out)
        handle._procs[rank] = subprocess.Popen(
            [sys.executable, script]
            + [str(a) for a in script_args],
            env=env, stdout=out, stderr=subprocess.STDOUT, cwd=REPO)
    return handle


# ---------------------------------------------------------------------------
# worker-side helpers (imported by the launched scripts)
# ---------------------------------------------------------------------------
def init() -> Tuple[int, int]:
    """Bring up this worker's jax runtime on the mesh: CPU platform,
    ``jax.distributed.initialize`` against the coordinator rank 0's
    endpoint (via distributed.env.init_parallel_env). Returns
    (rank, world)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from paddle_tpu.distributed.env import init_parallel_env

    env = init_parallel_env()
    return env.rank, env.world_size


def init_env_only() -> Tuple[int, int]:
    """(rank, world) from the PADDLE_* env protocol WITHOUT
    ``jax.distributed.initialize``. Container truth forcing this
    option: on jax 0.4.37, once the distributed runtime is up, even
    rank-LOCAL sharded work (a NamedSharding ``device_put``, the
    checkpoint layer's ``sync_global_devices`` barrier) routes through
    ``multihost_utils`` collectives that the CPU backend cannot run.
    Workers whose device compute is per-rank (the resilience mesh:
    replicated trainers + file-board consensus) run real processes
    with env-protocol ranks and leave jax in single-process mode."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return (int(os.environ.get("PADDLE_TRAINER_ID", "0")),
            int(os.environ.get("PADDLE_TRAINERS_NUM", "1")))


def _coord_client():
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("mp_mesh.init() first (single-process run?)")
    return client


def barrier(name: str, timeout_ms: int = 60000) -> None:
    """Coordination-service barrier across ALL ranks. Do not use after
    a chaos kill — a dead peer never arrives; use the consensus board's
    lease-based paths instead (that asymmetry is the point)."""
    _coord_client().wait_at_barrier(f"mpmesh_{name}", timeout_ms)


def kv_set(key: str, value: str) -> None:
    _coord_client().key_value_set(key, value)


def kv_get(key: str, timeout_ms: int = 60000) -> str:
    return _coord_client().blocking_key_value_get(key, timeout_ms)


def chaos_spec() -> Optional[Tuple[str, int, str, float]]:
    """Parsed CHAOS_ENV: (kind, rank, point, seconds) or None."""
    raw = os.environ.get(CHAOS_ENV)
    if not raw:
        return None
    parts = raw.split(":")
    if len(parts) < 3:
        raise ValueError(f"bad {CHAOS_ENV} spec {raw!r}")
    kind, rank, point = parts[0], int(parts[1]), parts[2]
    secs = float(parts[3]) if len(parts) > 3 else 3600.0
    if kind not in ("kill", "hang"):
        raise ValueError(f"bad {CHAOS_ENV} kind {kind!r}")
    return kind, rank, point, secs


def chaos_point(name: str, rank: Optional[int] = None) -> None:
    """Declare a named fault-injection site. If the mesh was launched
    with ``chaos="kill:<rank>:<name>"`` and this process is that rank,
    it DIES here (SIGKILL-style ``os._exit(137)`` — no cleanup, no
    goodbyes, exactly like an OOM kill); ``hang:<rank>:<name>[:s]``
    sleeps ``s`` seconds instead (a wedged peer, not a dead one)."""
    spec = chaos_spec()
    if spec is None:
        return
    kind, target, point, secs = spec
    if point != name:
        return
    if rank is None:
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if rank != target:
        return
    if kind == "kill":
        sys.stdout.write(f"[mp_mesh] rank {rank} chaos-killed at "
                         f"{name!r}\n")
        sys.stdout.flush()
        os._exit(KILL_EXIT)
    sys.stdout.write(f"[mp_mesh] rank {rank} chaos-hang {secs}s at "
                     f"{name!r}\n")
    sys.stdout.flush()
    time.sleep(secs)


def wait_for_files(paths: Sequence[str], timeout_s: float = 60.0) -> bool:
    """Poll until every path exists (True) or the timeout passes."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if all(os.path.exists(p) for p in paths):
            return True
        time.sleep(0.05)
    return all(os.path.exists(p) for p in paths)


def finish_last(ok_file: str, peer_ok_files: Sequence[str],
                timeout_s: float = 60.0) -> None:
    """Rank 0's epilogue: wait for the OTHER survivors' markers first
    (rank 0 hosts the coordination service — exiting early would kill
    them via socket closure), then write own marker and hard-exit.
    Exits nonzero when a peer marker never appears."""
    ok = wait_for_files(peer_ok_files, timeout_s)
    if not ok:
        sys.stdout.write(f"[mp_mesh] missing peer markers: "
                         f"{[p for p in peer_ok_files if not os.path.exists(p)]}\n")
    finish(ok_file if ok else None, 0 if ok else 1)


def finish(ok_file: Optional[str] = None, code: int = 0) -> None:
    """Worker epilogue: write the ok marker, flush, and ``os._exit`` —
    skipping the jax coordination service's interpreter-exit teardown,
    which errors or stalls whenever a peer was chaos-killed (its
    heartbeat has declared the job failed by then). The launcher judges
    workers by exit code + marker, so the hard exit IS the clean
    protocol here."""
    if ok_file:
        with open(ok_file, "w") as f:
            f.write("OK\n")
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def _main() -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="tools/mp_mesh.py",
        description="launch N real jax.distributed CPU processes")
    ap.add_argument("--nprocs", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--log-dir", default="/tmp/mp_mesh_logs")
    ap.add_argument("--chaos", default=None,
                    help="kill:<rank>:<point> | hang:<rank>:<point>[:s]")
    ap.add_argument("--expect-fail-ranks", default="",
                    help="comma-separated ranks allowed to die")
    ap.add_argument("--host-devices", type=int, default=1)
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    res = launch(args.nprocs, args.script, args.script_args,
                 log_dir=args.log_dir, timeout=args.timeout,
                 chaos=args.chaos,
                 expect_fail_ranks=tuple(
                     int(r) for r in args.expect_fail_ranks.split(",")
                     if r.strip()),
                 host_devices=args.host_devices)
    print(res.tail())
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(_main())
