"""Readings that set a cell's rate and limits, made on the chip in one
process (the benchmark's own runs do not run this).

    python3 bench/calibrate.py --workload yi6b.chat --sweep 4,6,8 --seconds 20
        the knee: the open loop at each rate, with the backlog (requests
        submitted and not finished) a third, two thirds and all the way
        through the window; the knee is the highest rate whose backlog
        does not grow.
    python3 bench/calibrate.py --workload yi6b.chat --seeds 1,2,3 --seconds 10
        per seed, the widest served-token gap of the program (the lower
        reading of ``max_logit_gap``) and of the controls, the reference
        rounded to int8 and to fp8 (the upper reading), on the same sample,
        each with the verdict ``run.py`` gives it under the cell's limits.

Each result is one JSON line on standard output and in
``chiprun_out/calibrate_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import check, run  # noqa: E402
from bench.metrics import percentile  # noqa: E402

#: a sweep point past the knee never drains; stop it after this long
SWEEP_DRAIN_S = 20.0


def backlog_at(rec, t: float) -> int:
    return sum(1 for tr in rec["tracks"] if tr.submit <= t and (tr.done is None or tr.done > t))


def sweep_line(rec, rate: float) -> dict:
    s = rec["seconds"]
    first = [tr.token_times[0] - tr.due for tr in rec["tracks"] if tr.token_times]
    return {"rate_per_s": rate, "submitted": len(rec["tracks"]),
            "backlog": [backlog_at(rec, s * f) for f in (1 / 3, 2 / 3, 1.0)],
            "ttft_p50_ms": 1000 * (percentile(first, 50) or 0),
            "ttft_p95_ms": 1000 * (percentile(first, 95) or 0),
            "output_tok_s": sum(1 for tr in rec["tracks"] for x in tr.token_times if x <= s) / s,
            "unfinished_after_drain": sum(1 for tr in rec["tracks"] if tr.done is None),
            "preemptions_of_finished": sum(tr.preemptions for tr in rec["tracks"])}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--sweep", help="comma-separated arrival rates (req/s)")
    p.add_argument("--seeds", help="comma-separated seeds")
    args = p.parse_args(argv)
    root = run.ROOT
    bench, cell, cfg, traffic, limits = run.spec.load_cell(root, args.workload)
    peaks = run.spec.load_json(root / "bench" / "peaks.json")
    sys.path.insert(0, str(root / "src"))
    run.count_compiles()
    dev = run.device_info(cell["chips"], peaks, require_tpu=True)
    run.enable_compile_cache(root)
    out = root / "chiprun_out" / f"calibrate_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)

    def emit(line):
        line = {"workload": args.workload, **line}
        print(json.dumps(line), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")

    run.cover(cfg, traffic)
    run.phase("shape cover")
    if args.sweep:
        weights = None
        for rate in (float(r) for r in args.sweep.split(",")):
            t = {**traffic, "arrivals": {**traffic["arrivals"], "rate_per_s": rate}}
            rec, weights = run.serve(cfg, t, 1, args.seconds, False, peaks, dict(dev), weights,
                                     drain_s=SWEEP_DRAIN_S)
            emit({"sweep": sweep_line(rec, rate), "compiles_in_window": rec["compiles_in_window"]})
    for seed in (int(s) for s in (args.seeds.split(",") if args.seeds else [])):
        rec, weights = run.serve(cfg, traffic, seed, args.seconds, False, peaks, dict(dev))
        sample = check.sample(rec["tracks"], seed)
        line = {"seed": seed, "sampled_requests": len(sample),
                "sampled_tokens": sum(len(t.output) for t in sample),
                "admission": check.admission(rec["tracks"], rec["loop"], rec["in_flight"]),
                "compiles_in_window": rec["compiles_in_window"],
                "window_steps": len(rec["window_steps"]),
                "metrics": {k: v["value"] for k, v in
                            run.metrics_for(bench, args.workload, False, rec).items()}}
        for quant in (None, "int8", "fp8"):
            checks, correct = run.verdict(rec, cfg, traffic, weights, limits, seed, quant)
            line[f"gap_{quant or 'program'}"] = checks["max_logit_gap"]["value"]
            line[f"correct_{quant or 'program'}"] = correct
        emit(line)
        del weights, rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
