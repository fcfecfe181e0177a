"""Readings that the limits of ``correct`` are set from, in one process.

    python -m chipbench.calibrate --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--faults half_batch,no_exchange] \\
        [--out FILE]

For every ``--seeds`` seed the program's compiled step (built once, as a
run builds it) takes its checked steps from that seed's weights; then the
plain reference runs each seed, and the control (the reference in fp8,
``reference.run(lowp=True)``) and each planted fault (``reference.run(
fault=...)``) run the ``--control-seeds``. Every line printed (and written
to ``--out``) is one JSON object: the kind, the seed and the compared
numbers; the last line sums up the largest program readings and the
smallest control and fault readings. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def collect(cell, devices, seeds, cseeds, faults, emit) -> dict:
    """Emit the program's readings on ``seeds`` and the control's and each
    fault's on ``cseeds``; return the summary (also emitted)."""
    from chipbench import compare, harness, reference

    prog = harness.build_program(cell)
    programs = {}
    for s in seeds:
        prog.reseed(s)
        programs[s] = harness.read_change(
            harness.checked_steps(prog, cell, s), s, cell.model)
    peak = harness.peak_bytes(devices)
    del prog
    gc.collect()
    emit({"kind": "program_peak_bytes", "value": peak})

    def ref(seed, **kw):
        return reference.run(cell.model, cell.workload["optimizer"],
                             cell.traffic, seed, cell.chips,
                             steps=harness.CHECKED_STEPS, **kw)

    worst, least = {}, {}
    for s in seeds + [c for c in cseeds if c not in seeds]:
        r = ref(s)
        if s in programs:
            nums = compare.readings(programs[s], r)
            emit({"kind": "program", "seed": s, **nums,
                  "losses": programs[s]["losses"],
                  "ref_losses": r["losses"],
                  "leaves": compare.leaf_table(programs[s], r)})
            for k, v in nums.items():
                worst[k] = max(worst.get(k, 0.0), v)
        if s not in cseeds:
            continue
        for kind, kw in [("control", {"lowp": True})] + [
                (f"fault:{f}", {"fault": f}) for f in faults]:
            other = ref(s, **kw)
            nums = compare.readings(other, r)
            emit({"kind": kind, "seed": s, **nums,
                  "leaves": compare.leaf_table(other, r)})
            for k, v in nums.items():
                least.setdefault(kind, {})
                least[kind][k] = min(least[kind].get(k, float("inf")), v)
    summary = {"kind": "summary", "program_max": worst, "min": least}
    emit(summary)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness, manifest
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    cell = manifest.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]
    out = open(args.out, "w") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    collect(cell, devices, seeds, cseeds, faults, emit)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
