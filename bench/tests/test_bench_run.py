"""Whole runs of the harness on the CPU at a tiny size: the chip check
refuses, a sound run is correct, and each fault planted under the timed
path turns ``correct`` false."""

import json

import pytest

from bench import check, run
from bench.tests import tiny


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """JAX reads its cache settings once per process: keep this test
    process's other tests off a cache in a temporary checkout."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "off in tests")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"), limit=1e-4)


def result(capsys, root, cell, seed=3, seconds=2, trace=0, require_tpu=False):
    rc = run.run(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                  "--trace", str(trace)], root=root, require_tpu=require_tpu)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def test_off_a_tpu_the_run_refuses_and_prints_no_result(capsys, root):
    rc, line = result(capsys, root, "tiny.open", require_tpu=True)
    assert rc != 0 and line is None


def test_without_the_program_the_run_refuses(capsys, tmp_path):
    bare = tiny.make_root(tmp_path)
    (bare / "src").unlink()
    rc, line = result(capsys, bare, "tiny.open")
    assert rc != 0 and line is None


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_a_sound_run_is_correct(capsys, root, cell):
    rc, line = result(capsys, root, cell, seed=2**31 + 11)
    assert rc == 0 and line["correct"] is True
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_logit_gap"]["value"] <= 1e-4
    assert {"itl_p95_ms", "output_tok_s", "setup_s"} <= set(line["metrics"])
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_traced_run_reports_layer_metrics(capsys, root):
    rc, line = result(capsys, root, "tiny.open", trace=1)
    assert rc == 0 and line["correct"] is True
    assert {"lane_occupancy", "kv_used_frac", "queue_wait_p95_ms"} <= set(line["metrics"])


def _host_steps_as_device_work(monkeypatch):
    """The CPU's trace has no TPU plane: take each ``bench.step`` as device
    work, so the readers of device time find steps to read."""
    from bench import trace as T
    load = T.load

    def loaded(path):
        events = load(path)
        events["device"] = {"/device:TPU:0": [(a, b, "step") for a, b, n in events["host"]
                                              if n == "bench.step"]}
        return events
    monkeypatch.setattr(T, "load", loaded)


def test_a_traced_moe_run_counts_through_its_layout(capsys, monkeypatch, tmp_path):
    from bench.layouts import dense, moe
    root = tiny.make_root(tmp_path, limit=1e-4)
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    _host_steps_as_device_work(monkeypatch)
    calls = {moe: 0, dense: 0}
    for module in calls:
        for name in ("decode_flops", "decode_bytes", "prefill_flops"):
            def counted(*args, _count=getattr(module, name), _module=module):
                calls[_module] += 1
                return _count(*args)
            monkeypatch.setattr(module, name, counted)
    rc, line = result(capsys, root, "tiny.moe.open", trace=1)
    assert rc == 0 and line["correct"] is True
    assert {"mfu", "decode_hbm_roofline"} <= set(line["metrics"])
    assert calls[moe] > 0 and calls[dense] == 0


def test_a_configuration_its_program_does_not_match_is_refused(capsys, tmp_path):
    """tiny_moe's file read as a dense layout: the program's block pattern
    and fields differ, so the run refuses before its window."""
    (tmp_path / "checkout").mkdir()
    root = tiny.make_root(tmp_path / "checkout")
    as_dense = tmp_path / "tiny_moe_as_dense.json"
    as_dense.write_text(json.dumps({**json.loads((tiny.DATA / "tiny_moe.json").read_text()),
                                    "layout": "dense"}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        if c["name"] == "tiny_moe":
            c["file"] = str(as_dense)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run.run(["--workload", "tiny.moe.open", "--seed", "3", "--seconds", "2",
                  "--trace", "0"], root=root, require_tpu=False)
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "under layout 'dense'" in err and "block_pattern" in err


def _armed(monkeypatch):
    """A flag raised once the harness steps its window (not in set-up)."""
    from bench import loop
    armed, step = [], loop.Driver.step

    def stepping(self):
        armed.append(True)
        return step(self)
    monkeypatch.setattr(loop.Driver, "step", stepping)
    return armed


def _altered_token(monkeypatch):
    """The engine hands out a token other than the one it computed."""
    from repro.serving import engine as E
    armed, step, done = _armed(monkeypatch), E.Engine.step, []

    def faulty(self):
        out = step(self)
        for r in self.active:
            if armed and r is not None and len(r.output) == 3 and not done:
                r.output[-1] = (r.output[-1] + 1) % self.cfg.vocab_size
                done.append(r.uid)
        return out
    monkeypatch.setattr(E.Engine, "step", faulty)


def _dropped_request(monkeypatch):
    """A request is admitted and never answered."""
    from repro.fabric import Fabric
    armed, step, dropped = _armed(monkeypatch), Fabric.step, []

    def faulty(self):
        out = step(self)
        if armed and out and not dropped:
            dropped.append(out.pop())
        return out
    monkeypatch.setattr(Fabric, "step", faulty)


def _short_answer(monkeypatch):
    """A request is answered with a token fewer than it asked for."""
    from repro.serving import engine as E
    armed, step, done = _armed(monkeypatch), E.Engine.step, []

    def faulty(self):
        out = step(self)
        if armed and out and not done:
            out[0].output.pop()
            done.append(out[0].uid)
        return out
    monkeypatch.setattr(E.Engine, "step", faulty)


def _cache_unchanged(monkeypatch):
    """The model step hands back a copy of the KV pools it was given, made
    before the call: its state is never updated, so tokens after the first
    read a stale cache. (A copy, not the pools themselves: a forward that
    donates its pools deletes them.)"""
    import jax.numpy as jnp

    from repro.serving import engine as E
    armed, make = _armed(monkeypatch), E.make_paged_forward

    def faulty_make(cfg):
        forward = make(cfg)

        def faulty(params, toks, *pools_and_tables):
            if not armed:
                return forward(params, toks, *pools_and_tables)
            kept = [jnp.copy(pool) for pool in pools_and_tables[:-2]]
            logits = forward(params, toks, *pools_and_tables)[0]
            return (logits, *kept)
        return faulty
    monkeypatch.setattr(E, "make_paged_forward", faulty_make)


@pytest.mark.parametrize("fault", [_altered_token, _dropped_request, _short_answer,
                                   _cache_unchanged])
@pytest.mark.parametrize("cell", tiny.CELLS)
def test_a_fault_under_the_timed_path_is_not_correct(capsys, monkeypatch, root, fault, cell):
    fault(monkeypatch)
    monkeypatch.setattr(check, "sample", lambda tracks, seed: [t for t in tracks if t.output])
    rc, line = result(capsys, root, cell, seed=5)
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.moe.open"])
def test_the_stale_cache_fault_holds_when_the_forward_donates_its_pools(capsys, monkeypatch,
                                                                        root, cell):
    """Under a forward that donates its KV pools, a sound run is correct and
    the planted stale cache still turns ``correct`` false."""
    import jax

    from repro.serving import engine as E
    from repro.serving import paged_model as PM

    def donating(cfg):
        return jax.jit(lambda p, t, kp, vp, bt, sl: PM.paged_forward(p, t, cfg, kp, vp, bt, sl),
                       donate_argnums=(2, 3))
    monkeypatch.setattr(E, "make_paged_forward", donating)
    monkeypatch.setattr(check, "sample", lambda tracks, seed: [t for t in tracks if t.output])
    rc, sound = result(capsys, root, cell, seed=5)
    assert rc == 0 and sound["correct"] is True
    _cache_unchanged(monkeypatch)
    rc, line = result(capsys, root, cell, seed=5)
    assert rc == 0 and line["correct"] is False
