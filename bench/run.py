"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``) and
a traffic mix (``bench/traffic``). The run makes the weights on the device
from the seed, opens the fabric at the configuration's serving geometry,
warms up every prompt length of the traffic through ``Fabric.submit`` /
``Fabric.step``, then measures for ``--seconds``: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from
the same window under the profiler. After the window it checks the served
tokens against the plain reference and admission (``check.py``), prints
each number compared beside its limit on standard error, and prints one
JSON line as the last line of standard output.

It exits non-zero, printing no result, when JAX's first device is not a
TPU listed in ``bench/peaks.json``, when there are fewer chips than the
cell asks for, when the serving program (``src/``) is missing, or when the
program's configuration or parameter layout differs from the file's, as
the file's layout (``bench/layouts``) reads it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, generator, layouts, loop, spec  # noqa: E402

#: after the window (and the profiler's stop), late requests are stepped
#: on for this long at most
DRAIN_S = 60.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: programs built (compiled, or loaded from the persistent cache) so far
COMPILES = [0]


def _count_compile(name, secs, **kw):
    if name == BACKEND_COMPILE_EVENT:
        COMPILES[0] += 1


def count_compiles() -> None:
    """Listen for program builds, once per process."""
    import jax

    if not COMPILES[1:]:
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        COMPILES.append(True)


class Refused(Exception):
    """The run cannot measure here; exit non-zero with no result."""


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def phase(name: str) -> None:
    """Log a set-up phase's end, in seconds since the process started."""
    log(f"{name} done at {time.perf_counter() - T_PROCESS:.1f}s")


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path in the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` points; small programs are kept too, so
    a later run of the cell loads every program it needs."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, peaks: dict, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise Refused(f"JAX's first device is {dev.platform!r} ({dev.device_kind}), not a TPU")
        if dev.device_kind not in peaks["devices"]:
            raise Refused(f"device kind {dev.device_kind!r} has no peaks in bench/peaks.json")
        if len(devs) < chips:
            raise Refused(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def check_program(cfg: dict, model_cfg, weights) -> None:
    """Refuse to serve when the program's configuration or parameter layout
    differs from the configuration file's, as its layout module reads it."""
    import jax

    from repro.models import init_params

    layout = layouts.name(cfg)
    want = layouts.load(cfg).program_fields(cfg)
    got = {k: getattr(model_cfg, k, "(no such attribute)") for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise Refused(f"the program's {model_cfg.name} differs from {cfg['name']}.json "
                      f"under layout {layout!r}: {bad}")
    theirs = jax.eval_shape(init_params, model_cfg, jax.random.PRNGKey(0))
    shape = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), theirs)
    ours = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    if shape != ours:
        raise Refused(f"the program's parameter layout differs from bench/layouts/{layout}.py")


def open_fabric(cfg: dict, weights, trace: bool, shadow: bool = False):
    from repro.fabric import Fabric, FabricConfig
    from repro.obs import ObsConfig

    geo = cfg["serving"]
    obs = ObsConfig(trace_rate=1.0, ring_capacity=1 << 20) if trace else None
    config = FabricConfig(arch=cfg["arch"], smoke=shadow or bool(cfg.get("smoke", False)),
                          max_batch=geo["max_batch"], page_size=geo["page_size"],
                          num_pages=geo["num_pages"], max_seq=geo["max_seq"],
                          kv_window=geo["kv_window"], obs=obs)
    return Fabric.open(config, params=weights)


def kv_probe(fab):
    """Share of KV pages not free, over the engines (traced runs only: it
    reads the pool on the device after each step)."""
    def probe():
        pools = [getattr(e, "pool", None) for e in fab.engines]
        if not pools or any(p is None for p in pools):
            return None
        return sum(1 - p.free_pages() / p.num_pages for p in pools) / len(pools)
    return probe


def queue_waits(fab, since: float):
    """Flight recorder: seconds from submit to seat per request submitted
    after ``since`` (time.monotonic)."""
    hub = fab.obs
    if hub is None:
        return None
    submit, seat = {}, {}
    for t, stage, cls, seq, *_ in hub.events():
        if stage == "submit" and t >= since:
            submit[(cls, seq)] = t
        elif stage == "seat":
            seat.setdefault((cls, seq), t)
    return [seat[k] - t for k, t in submit.items() if k in seat]


def cover(cfg: dict, traffic: dict) -> None:
    """Shape coverage on a shadow fabric (``loop.cover_shapes``)."""
    geo = cfg["serving"]
    shadow = open_fabric(cfg, None, False, shadow=True)
    loop.cover_shapes(shadow, generator.length_values(traffic["prompt_len"]),
                      generator.length_values(traffic["output_len"]), geo["max_batch"],
                      geo["page_size"], geo["num_pages"], shadow.model_cfg.vocab_size)
    shadow.close()
    del shadow
    gc.collect()


def serve(cfg: dict, traffic: dict, seed: int, seconds: float, trace: bool,
          peaks: dict, dev: dict, weights=None, drain_s: float = DRAIN_S):
    """Fabric, warm-up, window and drain; returns the run record and the
    weights (made from ``seed`` unless given)."""
    import jax

    from bench import weights as W

    geo = cfg["serving"]
    if weights is None:
        weights = W.make(cfg, seed)
        jax.block_until_ready(weights)
        phase("weights")
    fab = open_fabric(cfg, weights, trace)
    check_program(cfg, fab.model_cfg, weights)
    phase("fabric open")
    loop.warm_up(fab, generator.length_values(traffic["prompt_len"]), cfg["vocab_size"], seed)
    phase("warm-up")
    reqs = generator.generate(traffic, seed, seconds, cfg["vocab_size"])
    drv = loop.Driver(fab, annotate=trace, kv_probe=kv_probe(fab) if trace else None)
    if trace:
        drv._kv_probe()  # build its program before the window
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    mono0 = time.monotonic()
    setup_s = time.perf_counter() - T_PROCESS
    n_compiles = COMPILES[0]
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with (jax.profiler.TraceAnnotation("bench.window") if trace_dir
              else contextlib.nullcontext()):
            if traffic["loop"] == "open":
                loop.run_open(drv, reqs, seconds)
            else:
                loop.run_closed(drv, reqs, seconds,
                                         generator.backlog(traffic, geo["max_batch"]))
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    in_window = COMPILES[0] - n_compiles
    n_window_steps = len(drv.steps)
    if traffic["loop"] == "open":  # the drain, after the profiler stopped
        until = drv.now() + drain_s
        while not drv.idle() and drv.now() < until:
            drv.step()
    phase("window and drain")
    # requests the fabric still holds, in lanes or queued
    in_flight = fab.pending() + sum(r is not None for e in fab.engines for r in e.active)
    rec = {"seconds": seconds, "end": drv.now(), "loop": traffic["loop"],
           "tracks": drv.tracks, "steps": drv.steps, "setup_s": setup_s, "cfg": cfg,
           "peaks": peaks["devices"].get(dev["kind"]), "max_batch": geo["max_batch"],
           "compiles_in_window": in_window, "in_flight": in_flight,
           "window_steps": drv.steps[:n_window_steps],
           "queue_waits": queue_waits(fab, mono0) if trace else None, "trace": None}
    if trace_dir:
        from bench import trace as T
        path = T.find(trace_dir)
        rec["trace"] = T.reduce(T.load(path)) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev["memory_peak_bytes"] = memory_peak(jax.devices())
    fab.close()
    del fab, drv
    gc.collect()
    return rec, weights


def metrics_for(bench: dict, workload: str, trace: bool, rec: dict) -> dict:
    out = {}
    group = bench["per_layer"] if trace else bench["end_to_end"]
    for m in group:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        if m["name"] == "setup_s":
            value = rec["setup_s"]
        else:
            value = importlib.import_module(f"bench.metrics.{m['name']}").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        bench, cell, cfg, traffic, limits = spec.load_cell(root, args.workload)
        peaks = spec.load_json(root / "bench" / "peaks.json")
        if not (root / "src" / "repro").is_dir():
            raise Refused(f"no serving program under {root / 'src'}")
        sys.path.insert(0, str(root / "src"))
        count_compiles()
        dev = device_info(cell["chips"], peaks, require_tpu)
        phase("device")
        log(f"device {dev['kind']} x{dev['count']}; compile cache {enable_compile_cache(root)}")
    except (Refused, spec.SpecError) as e:
        log(f"refused: {e}")
        return 2
    cover(cfg, traffic)
    phase("shape cover")
    try:
        rec, weights = serve(cfg, traffic, args.seed, args.seconds, bool(args.trace), peaks, dev)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    log(f"window {args.seconds}s: {len(rec['steps'])} steps, "
        f"{sum(1 for t in rec['tracks'] if t.done is not None)} requests done, "
        f"{rec['compiles_in_window']} programs compiled or loaded inside the window")
    metrics = metrics_for(bench, args.workload, bool(args.trace), rec)
    if args.trace and rec["trace"] is not None:
        dev["busy_s"], dev["window_s"] = rec["trace"]["busy_s"], rec["trace"]["window_s"]
    checks, correct = verdict(rec, cfg, traffic, weights, limits, args.seed)
    phase("check")
    del weights
    attempted = [t for t in rec["tracks"] if t.due < rec["seconds"]]
    failed = sum(1 for t in attempted if t.uid is None or
                 (rec["loop"] == "open" and not t.token_times))
    line = {"correct": correct, "attempted": len(attempted), "failed": failed,
            "metrics": metrics, "device": dev}
    if args.trace and rec["trace"] is not None:
        line["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


def verdict(rec, cfg, traffic, weights, limits, seed, quant=None):
    """Each number compared with its limit, and whether all hold; with
    ``quant`` the control (``check.widest_gaps``) stands in the program's
    place."""
    sample = check.sample(rec["tracks"], seed)
    gaps = check.widest_gaps(cfg, weights, sample, check.padded_length(traffic), quant)
    counts = check.admission(rec["tracks"], rec["loop"], rec["in_flight"])
    checks = {"max_logit_gap": {"value": max(gaps) if gaps else None,
                                "limit": limits.get("max_logit_gap")}}
    checks.update({k: {"value": v, "limit": 0} for k, v in counts.items()})
    ok = (checks["max_logit_gap"]["value"] is not None
          and checks["max_logit_gap"]["limit"] is not None
          and checks["max_logit_gap"]["value"] <= checks["max_logit_gap"]["limit"]
          and all(v == 0 for v in counts.values()))
    return checks, bool(ok)


if __name__ == "__main__":
    sys.exit(run())
