#!/usr/bin/env python
"""Job launcher: spawn N framework processes with the dist env protocol.

Reference: ``tools/launch.py`` + ``dmlc_tracker/local.py`` (SURVEY.md §2.3
Tools row, §2.4 P3) — the local-mode tracker that starts workers with
DMLC_* env vars and supervises them.

TPU-native redesign: there is no server role to schedule — every process
is a worker; process 0 doubles as the JAX coordination-service host.  The
launcher's remaining jobs are (a) the env handshake, (b) output fan-in,
and (c) **failure detection with clean abort** (SURVEY.md §5.3): the first
worker to die takes the whole job down (SIGTERM, then SIGKILL) instead of
leaving the others hung in a collective.

Usage::

    python tools/launch.py -n 4 [--coordinator 127.0.0.1:9876] \
        python train.py --epochs 10

Workers read the handshake via ``mxnet_tpu.parallel.dist.initialize()``
(no arguments).

Every local worker gets the SAME environment and no device assignment,
so this local fan-out is the CPU/gloo test path (``JAX_PLATFORMS=cpu``).
On a TPU host a chip belongs to one process at a time — only the first
worker would get it — and one process drives all chips of a host: start
one worker per host there.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pump(proc, rank, stream_name):
    stream = getattr(proc, stream_name)
    prefix = f"[worker-{rank}] ".encode()
    out = getattr(sys, stream_name).buffer
    for line in iter(stream.readline, b""):
        out.write(prefix + line)
        out.flush()


def launch(n: int, cmd, coordinator: str = None, env_extra=None,
           timeout: float = None) -> int:
    """Spawn n workers; returns the job's exit code (0 iff all succeed)."""
    coordinator = coordinator or f"127.0.0.1:{_free_port()}"
    procs = []
    pumps = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(env_extra or {})
        env.update({
            "MXNET_TPU_COORDINATOR": coordinator,
            "MXNET_TPU_NUM_PROCS": str(n),
            "MXNET_TPU_PROC_ID": str(rank),
            # reference-compatible names for ported scripts
            "DMLC_NUM_WORKER": str(n),
            "DMLC_WORKER_ID": str(rank),
        })
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        procs.append(p)
        t = threading.Thread(target=_pump, args=(p, rank, "stdout"),
                             daemon=True)
        t.start()
        pumps.append(t)

    # failure detection: first non-zero exit (or timeout) aborts the job
    deadline = time.monotonic() + timeout if timeout else None
    failed_rank = None
    rc = 0
    try:
        while True:
            alive = False
            for rank, p in enumerate(procs):
                code = p.poll()
                if code is None:
                    alive = True
                elif code != 0 and failed_rank is None:
                    failed_rank = rank
                    rc = code
            if failed_rank is not None or not alive:
                break
            if deadline and time.monotonic() > deadline:
                failed_rank = -1
                rc = 124
                break
            time.sleep(0.1)
    finally:
        if failed_rank is not None:
            sys.stderr.write(
                f"launch: {'timeout' if failed_rank == -1 else f'worker-{failed_rank} exited rc={rc}'}"
                f" — aborting remaining workers\n")
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            t_end = time.monotonic() + 10
            for p in procs:
                try:
                    p.wait(timeout=max(0.1, t_end - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for t in pumps:
            t.join(timeout=2)
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch an N-process mxnet_tpu job (local mode)")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0's coordination service "
                         "(default: a free local port)")
    ap.add_argument("--timeout", type=float, default=None,
                    help="kill the job after this many seconds")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for workers (repeatable)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no worker command given")
    extra = dict(kv.split("=", 1) for kv in args.env)
    return launch(args.num_workers, args.command,
                  coordinator=args.coordinator, env_extra=extra,
                  timeout=args.timeout)


if __name__ == "__main__":
    sys.exit(main())
