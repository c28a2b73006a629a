"""Kill-9 crash-recovery driver for the durable index (docs/persistence.md).

The in-process fault harness (tests/faults.py) simulates crashes by raising
at an I/O step; this tool is the real thing: a CHILD process runs a seeded
scripted mutation workload against a durable directory, printing an ack
line after every mutation the WAL has fsync'd; the PARENT SIGKILLs it at a
chosen ack (no atexit, no flushing, no goodbye), reopens the directory via
``persist.open_engine``, and asserts the recovered engine's search results
are bit-identical to a from-scratch engine replaying exactly the
acknowledged prefix of the same workload.

The workload is pure-deterministic from ``--seed`` (same dataset build,
same mutation stream), so parent and child derive identical ops without
sharing anything but the directory under test.

Usage:
    python tools/crash_test.py [--kill-at 5] [--steps 12] [--seed 7] \
        [--dir /tmp/crashdir] [--sweep] [--replication]

``--kill-at N`` kills after the N-th ack (default: seeded random step).
``--sweep`` runs every kill point 1..steps sequentially. Exits non-zero on
any recovery mismatch.

``--replication`` runs the failover drill instead (docs/serving.md): the
child is a PRIMARY that ships every mutation's WAL segment through a
``DirTransport`` before acking; the parent SIGKILLs it mid-stream, replays
the shipped chain into a warm STANDBY, promotes it (term bump), and
asserts (a) the promoted replica is bit-identical to a from-scratch
rebuild over exactly the acked prefix, (b) the deposed primary's next
append and ship both raise ``FencedError``, and (c) standby reads serve
before, during, and after the transition.

The drill tests durability, not the device: the parent and every child run
JAX on the CPU (``JAX_PLATFORMS=cpu``, set in ``main`` and passed to each
child explicitly). Two JAX processes alive at once — the parent holding an
engine while its next child builds one — could not share one accelerator
chip, which belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import tempfile
import time

_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

NLIST = 16
D = 32
M = 8
ACK = "ACK"


def _cpu_env() -> dict[str, str]:
    """Child environment: this process's, with JAX pinned to the CPU."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def build_engine():
    import jax
    import jax.numpy as jnp

    from repro.core import ivf
    from repro.data import vectors
    from repro.engine import EngineConfig, SearchEngine

    ds = vectors.make_sift_like(n=2000, nt=1000, nq=6, d=D, ncl=16, seed=5)
    index = ivf.build_ivf(jax.random.PRNGKey(0), jnp.asarray(ds.train),
                          jnp.asarray(ds.base), m=M, nlist=NLIST,
                          coarse_iters=4, pq_iters=4)
    eng = SearchEngine(index, base=jnp.asarray(ds.base),
                       config=EngineConfig(nprobe=6, rerank_mult=2))
    return ds, eng


def scripted_ops(steps: int, seed: int):
    """Deterministic mutation stream; every op logs exactly one WAL record
    (delete slabs are disjoint so each always finds live rows)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ops = []
    for i in range(steps):
        r = i % 4
        if r == 3:
            ops.append(("compact",))
        elif r == 1:
            ops.append(("delete", np.arange(60 * i, 60 * i + 40)))
        else:
            ids = np.arange(2000 + 50 * i, 2000 + 50 * i + 30)
            ops.append(("upsert", ids,
                        rng.normal(size=(30, D)).astype(np.float32)))
    return ops


def apply_op(eng, op):
    if op[0] == "upsert":
        eng.upsert(op[1], op[2])
    elif op[0] == "delete":
        eng.delete(op[1])
    else:
        eng.compact()


def child_main(directory: str, steps: int, seed: int) -> int:
    """Run the workload, printing one ack per durably-logged mutation."""
    from repro import persist

    _ds, eng = build_engine()
    persist.ensure_attached(eng, directory)
    print(f"{ACK} 0", flush=True)  # attached: snapshot + WAL live
    for i, op in enumerate(scripted_ops(steps, seed), start=1):
        apply_op(eng, op)
        # the WAL record was fsync'd before the in-memory swap, so this op
        # survives any crash from here on — THAT is what the ack promises
        print(f"{ACK} {i}", flush=True)
    return 0


def child_repl_main(directory: str, steps: int, seed: int) -> int:
    """Primary-side workload: every mutation is shipped before it is acked,
    so an ack promises the op is replayable on the standby side."""
    from repro import persist

    primary_dir = os.path.join(directory, "primary")
    ship_dir = os.path.join(directory, "ship")
    _ds, eng = build_engine()
    persist.ensure_attached(eng, primary_dir)
    transport = persist.DirTransport(ship_dir)
    shipper = persist.WALShipper(eng, primary_dir, transport, term=0)
    shipper.ship_once()
    print(f"{ACK} 0", flush=True)  # snapshot + WAL live, chain shipped
    for i, op in enumerate(scripted_ops(steps, seed), start=1):
        apply_op(eng, op)
        shipper.ship_once()
        print(f"{ACK} {i}", flush=True)
    return 0


def run_replication(kill_at: int, steps: int, seed: int,
                    directory: str) -> bool:
    """Kill a shipping primary mid-stream, promote a warm standby, and
    check the three failover guarantees (see module docstring)."""
    import numpy as np

    from repro import persist
    from repro.persist.errors import FencedError

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--replication", "--dir", directory, "--steps", str(steps),
         "--seed", str(seed)],
        stdout=subprocess.PIPE, text=True, env=_cpu_env(),
        cwd=str(pathlib.Path(__file__).resolve().parent.parent))
    acked = -1
    try:
        for line in proc.stdout:
            if not line.startswith(ACK):
                continue
            acked = int(line.split()[1])
            if acked >= kill_at:
                os.kill(proc.pid, signal.SIGKILL)
                break
    finally:
        proc.stdout.close()
        proc.wait(timeout=60)
    if acked < kill_at:
        print(f"FAIL kill_at={kill_at}: primary finished after {acked} acks "
              "before the kill landed (raise --steps)")
        return False

    ship_dir = os.path.join(directory, "ship")
    standby_dir = os.path.join(directory, "standby")
    transport = persist.DirTransport(ship_dir)
    ds, standby = build_engine()
    replica = persist.StandbyReplica(standby, transport)
    q = np.asarray(ds.queries)

    def read_ok(eng, when):
        try:
            r = eng.search(q, 10)
            _ = np.asarray(r.ids)
            return True
        except Exception as exc:  # noqa: BLE001 — the drill reports, not raises
            print(f"FAIL kill_at={kill_at}: standby read errored {when}: "
                  f"{exc!r}")
            return False

    # (c) standby reads serve before, during, and after the transition
    if not read_ok(standby, "before replay"):
        return False
    replica.poll_once()
    if not read_ok(standby, "after replay, before promote"):
        return False
    if replica.applied_seq < acked:
        print(f"FAIL kill_at={kill_at}: primary acked {acked} shipped ops "
              f"but standby replayed only to seq {replica.applied_seq}")
        return False

    t0 = time.monotonic()
    new_term = replica.promote(standby_dir)
    dt = time.monotonic() - t0
    if not read_ok(standby, "after promote"):
        return False

    # (a) promoted replica == from-scratch rebuild of the acked prefix
    ops = scripted_ops(steps, seed)
    _ds2, ref = build_engine()
    for op in ops[:replica.applied_seq]:
        apply_op(ref, op)
    ra = standby.search(q, 10)
    rb = ref.search(q, 10)
    if (np.asarray(ra.ids) != np.asarray(rb.ids)).any() or \
       (np.asarray(ra.dists) != np.asarray(rb.dists)).any():
        print(f"FAIL kill_at={kill_at}: promoted standby (seq "
              f"{replica.applied_seq}) differs from the from-scratch "
              "replay of the same prefix")
        return False

    # (b) the deposed primary is fenced on its next append AND ship
    old = persist.open_engine(os.path.join(directory, "primary"))[0]
    old._wal.guard = persist.make_fence_guard(transport, 0)
    old_shipper = persist.WALShipper(old, os.path.join(directory, "primary"),
                                     transport, term=0)
    try:
        old_shipper.ship_once()
        print(f"FAIL kill_at={kill_at}: deposed primary shipped at term 0 "
              f"after promotion to term {new_term}")
        return False
    except FencedError:
        pass
    try:
        old.delete(np.arange(3))
        print(f"FAIL kill_at={kill_at}: deposed primary appended at term 0 "
              f"after promotion to term {new_term}")
        return False
    except FencedError:
        pass

    print(f"ok kill_at={kill_at}: acked>={acked}, standby replayed seq "
          f"{replica.applied_seq}, promoted to term {new_term} in {dt:.2f}s "
          "— bit-identical, deposed primary fenced")
    return True


def run_one(kill_at: int, steps: int, seed: int, directory: str) -> bool:
    """Spawn the child, SIGKILL it after ack ``kill_at``, verify recovery."""
    import numpy as np

    from repro import persist

    shutil.rmtree(directory, ignore_errors=True)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--dir", directory, "--steps", str(steps), "--seed", str(seed)],
        stdout=subprocess.PIPE, text=True, env=_cpu_env(),
        cwd=str(pathlib.Path(__file__).resolve().parent.parent))
    acked = -1
    try:
        for line in proc.stdout:
            if not line.startswith(ACK):
                continue
            acked = int(line.split()[1])
            if acked >= kill_at:
                os.kill(proc.pid, signal.SIGKILL)
                break
    finally:
        proc.stdout.close()
        proc.wait(timeout=60)
    if acked < kill_at:
        print(f"FAIL kill_at={kill_at}: child finished after {acked} acks "
              "before the kill landed (raise --steps)")
        return False

    t0 = time.monotonic()
    rec, info = persist.open_engine(directory, attach=False)
    dt = time.monotonic() - t0
    # the kill may land after further unread acks: the WAL, not the pipe,
    # is the source of truth — recovery must cover at least every ack we
    # READ, and whatever suffix was durable beyond it
    if info.last_seq < acked:
        print(f"FAIL kill_at={kill_at}: child acked {acked} mutations but "
              f"recovery replayed only to seq {info.last_seq} — ack lost")
        return False
    ops = scripted_ops(steps, seed)
    ds, ref = build_engine()
    for op in ops[:info.last_seq]:
        apply_op(ref, op)
    q = np.asarray(ds.queries)
    ra = rec.search(q, 10)
    rb = ref.search(q, 10)
    if (np.asarray(ra.ids) != np.asarray(rb.ids)).any() or \
       (np.asarray(ra.dists) != np.asarray(rb.dists)).any():
        print(f"FAIL kill_at={kill_at}: recovered state (seq {info.last_seq})"
              " differs from the from-scratch replay of the same prefix")
        return False
    print(f"ok kill_at={kill_at}: acked>={acked}, recovered seq "
          f"{info.last_seq} (snapshot {info.snapshot!r}, replayed "
          f"{info.replayed}, wal tail truncated {info.truncated_bytes}B) "
          f"in {dt:.2f}s — bit-identical")
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--dir", default=None,
                    help="durable directory (default: fresh tempdir)")
    ap.add_argument("--steps", type=int, default=12,
                    help="mutations in the scripted workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--kill-at", type=int, default=None,
                    help="SIGKILL after this ack (default: seeded random)")
    ap.add_argument("--sweep", action="store_true",
                    help="run every kill point 1..steps")
    ap.add_argument("--replication", action="store_true",
                    help="run the ship/promote failover drill instead")
    args = ap.parse_args()
    # before the first jax import (all of them are inside the functions)
    os.environ["JAX_PLATFORMS"] = "cpu"

    if args.child:
        if args.replication:
            return child_repl_main(args.dir, args.steps, args.seed)
        return child_main(args.dir, args.steps, args.seed)

    tmp = None
    directory = args.dir
    if directory is None:
        tmp = tempfile.mkdtemp(prefix="crash_test_")
        directory = tmp
    try:
        if args.sweep:
            points = list(range(1, args.steps + 1))
        else:
            import random
            kill_at = (args.kill_at if args.kill_at is not None
                       else random.Random(args.seed).randint(1, args.steps))
            points = [kill_at]
        run = run_replication if args.replication else run_one
        failures = sum(not run(p, args.steps, args.seed, directory)
                       for p in points)
        if failures:
            print(f"{failures}/{len(points)} kill points FAILED")
            return 1
        print(f"all {len(points)} kill point(s) recovered bit-identical")
        return 0
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
