"""Dry-run sweep driver: every (arch × shape × mesh) cell via subprocesses.

Each cell runs in its own process (fresh XLA, crash isolation) and appends
one JSON record to the output file; the sweep is resumable — cells already
recorded are skipped. Skipped-by-applicability cells are recorded too, so
the output accounts for all 40 assigned cells per mesh.

A CPU-only tool: each cell's child process fakes 512 CPU devices. Keep it
off the chip path — a child that reached for the TPU would find it held by
its parent, and chip runs use one process (`chip_smoke.py`).

Usage:
  PYTHONPATH=src python -m repro.launch.sweep --out results/dryrun.jsonl \
      [--mesh single|multi|both] [--arch <id> ...] [--timeout 1800]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from repro.configs import ARCH_IDS, SHAPES, applicable


def load_done(path: Path):
    done = set()
    if path.exists():
        for line in path.read_text().splitlines():
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if r.get("status") in ("ok", "skipped"):
                done.add((r["arch"], r["shape"], r["mesh"], r.get("quant", "none")))
    return done


def run_cell(arch, shape, mesh, out, timeout, quant="none", extra=()):
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--mesh", mesh,
        "--quant", quant, "--out", str(out), *extra,
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, env=env, timeout=timeout,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        status = "ok" if proc.returncode == 0 else "error"
        tail = proc.stdout[-1500:]
    except subprocess.TimeoutExpired:
        status, tail = "timeout", ""
        with open(out, "a") as f:
            f.write(json.dumps({
                "arch": arch, "shape": shape, "mesh": mesh, "quant": quant,
                "status": "timeout", "timeout_s": timeout,
            }) + "\n")
    print(f"[sweep] {arch} × {shape} × {mesh} ({quant}): {status} "
          f"({time.time()-t0:.0f}s)", flush=True)
    if status == "error":
        print(tail, flush=True)
    return status


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    done = load_done(out)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = args.arch or list(ARCH_IDS)
    shapes = args.shape or list(SHAPES)

    cells = []
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mesh))
    # Cheap cells first (decode before prefill/train is not knowable a
    # priori; order by arch size proxy = param count asc so failures in
    # small archs surface early).
    from repro.configs import get_config

    cells.sort(key=lambda c: (get_config(c[0]).param_count(), c[1]))

    n_done = n_err = 0
    for arch, shape, mesh in cells:
        if (arch, shape, mesh, "none") in done:
            continue
        ok, reason = applicable(arch, shape)
        if not ok:
            with open(out, "a") as f:
                f.write(json.dumps({
                    "arch": arch, "shape": shape, "mesh": mesh, "quant": "none",
                    "status": "skipped", "reason": reason,
                }) + "\n")
            print(f"[sweep] {arch} × {shape} × {mesh}: skipped ({reason})",
                  flush=True)
            continue
        status = run_cell(arch, shape, mesh, out, args.timeout)
        n_done += status == "ok"
        n_err += status != "ok"
    print(f"[sweep] finished: {n_done} ok, {n_err} failed", flush=True)


if __name__ == "__main__":
    main()
