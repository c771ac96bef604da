"""The latent-attention MoE layout (``layouts/mla_moe.py``) in whole runs
of the harness on the CPU, on the program's DeepSeek-V2-Lite smoke model:
a sound run is correct, the int8 and fp8 controls fail where the program
passes, each planted fault turns ``correct`` false, a program that holds
another number of experts is refused; and the layout's counts at the
published widths."""

import json
import math
import sys

import jax
import pytest

from bench import check, counts, run
from bench import weights as W
from bench.layouts import mla_moe
from bench.metrics import mla_decode_roofline
from bench.tests import test_bench_run as planted
from bench.tests import tiny

LIMIT = 1e-4
CELLS = ["tiny.mla.open", "tiny.mla.closed"]
CONFIG = tiny.REPO / "bench" / "configs" / "deepseek_v2_lite.json"


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "off in tests")


def make_root(tmp, config=tiny.DATA / "tiny_mla_moe.json"):
    """``tiny.make_root`` plus the cells ``tiny.mla.open`` and
    ``tiny.mla.closed`` on ``config``."""
    root = tiny.make_root(tmp, limit=LIMIT)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_mla_moe", "source": "tests", "file": str(config),
                             "reduced": [], "why": "tests"})
    for cell in CELLS:
        loop = cell.rsplit(".", 1)[1]
        bench["workloads"].append({"name": cell, "config": "tiny_mla_moe",
                                   "traffic": f"tiny_{loop}", "chips": 1, "why": "tests"})
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"max_logit_gap": LIMIT}))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(capsys, root, cell):
    rc, line = planted.result(capsys, root, cell, seed=2**31 + 16)
    assert rc == 0 and line["correct"] is True
    assert line["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert {"itl_p95_ms", "output_tok_s", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Seeds 1, 2 and 3 of ``tiny.mla.open`` served."""
    root = make_root(tmp_path_factory.mktemp("checkout"))
    if str(tiny.REPO / "src") not in sys.path:
        sys.path.insert(0, str(tiny.REPO / "src"))
    _, _, cfg, traffic, _ = run.spec.load_cell(root, "tiny.mla.open")
    run.count_compiles()
    dev = run.device_info(1, {}, require_tpu=False)
    run.cover(cfg, traffic)
    out = {}
    for seed in (1, 2, 3):
        rec, weights = run.serve(cfg, traffic, seed, 2.0, False, {"devices": {}}, dict(dev))
        out[seed] = (cfg, traffic, rec, weights)
    return out


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(served, seed, quant):
    """The reference rounded to int8 or fp8 (router included), in the
    program's place at each served position, reads above the limit."""
    cfg, traffic, rec, weights = served[seed]
    sample = [t for t in rec["tracks"] if t.output]
    length = check.padded_length(traffic)
    program = max(check.widest_gaps(cfg, weights, sample, length))
    control = max(check.widest_gaps(cfg, weights, sample, length, quant))
    assert program <= LIMIT < control


@pytest.mark.parametrize("fault", [planted._altered_token, planted._dropped_request,
                                   planted._short_answer, planted._cache_unchanged])
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_under_the_timed_path_is_not_correct(capsys, monkeypatch, root, fault, cell):
    fault(monkeypatch)
    monkeypatch.setattr(check, "sample", lambda tracks, seed: [t for t in tracks if t.output])
    rc, line = planted.result(capsys, root, cell, seed=5)
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("held", [2, 8])
def test_a_program_holding_another_number_of_experts_is_refused(capsys, tmp_path, held):
    """The file says this rank holds ``held`` experts; the program's smoke
    model holds 4: the run refuses before its window, naming the field."""
    cfg = json.loads((tiny.DATA / "tiny_mla_moe.json").read_text())
    other = tmp_path / "tiny_mla_moe_other.json"
    other.write_text(json.dumps({**cfg, "n_routed_experts": held}))
    (tmp_path / "checkout").mkdir()
    root = make_root(tmp_path / "checkout", other)
    rc = run.run(["--workload", "tiny.mla.open", "--seed", "3", "--seconds", "2",
                  "--trace", "0"], root=root, require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 2 and out.strip() == ""
    assert "under layout 'mla_moe'" in err and "experts_held" in err


def test_a_traced_run_counts_through_its_layout(capsys, monkeypatch, tmp_path):
    """On the CPU's trace (each ``bench.step`` taken as device work) the
    all-cell roofline metrics are counted by ``mla_moe``; the MLA kernel's
    share finds no kernel op and is left out, without error."""
    root = make_root(tmp_path)
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    planted._host_steps_as_device_work(monkeypatch)
    calls = []
    for name in ("decode_flops", "decode_bytes", "prefill_flops"):
        def counted(*args, _count=getattr(mla_moe, name)):
            calls.append(1)
            return _count(*args)
        monkeypatch.setattr(mla_moe, name, counted)
    rc, line = planted.result(capsys, root, "tiny.mla.open", trace=1)
    assert rc == 0 and line["correct"] is True
    assert {"mfu", "decode_hbm_roofline"} <= set(line["metrics"])
    assert "mla_decode_roofline" not in line["metrics"] and calls


def _step(context):
    from bench.loop import Step
    return Step(0.0, 0.01, 0, len(context), list(context), [])


def test_the_mla_roofline_reads_the_kernels_ops_only():
    """The kernel's ops (by name) against the least time of every decoding
    step's latent attention; no kernel op, no trace or no peaks: None."""
    cfg = json.loads(CONFIG.read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [_step([1000] * 48), _step([1200] * 48), _step([])]
    rec = {"cfg": cfg, "peaks": peaks, "window_steps": steps,
           "trace": {"device_ops": [["_while.2", 9.0], ["_mla_decode_attention.5___bf16", 0.004],
                                    ["_mla_decode_attention.6___bf16", 0.006]]}}
    least = sum(max(sum(mla_moe.attention_flops(cfg, c) for c in s.context) / 197e12,
                    sum(c * 31104 for c in s.context) / 819e9) for s in steps[:2])
    assert mla_decode_roofline.read(rec) == pytest.approx(100 * least / 0.010)
    assert 0 < mla_decode_roofline.read(rec) <= 100
    for missing in ({"trace": {"device_ops": [["_paged_decode_attention.5", 1.0]]}},
                    {"trace": None}, {"peaks": None}):
        assert mla_decode_roofline.read({**rec, **missing}) is None


def test_counts_at_the_published_widths():
    """By hand at DeepSeek-V2-Lite's widths, 8 of 64 experts held: 3.11 B
    parameters; 576 values of 2 B per token and layer, 31,104 B over 27;
    absorbed attention 27 * 2 * 16 * (512 + 64 + 512) per key; a token
    multiplies through 6 * 8/64 held experts, the shared expert and the
    dense layer."""
    cfg = json.loads(CONFIG.read_text())
    leaves = jax.tree_util.tree_leaves(W.shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    total = sum(math.prod(s[0] if len(s) == 2 and isinstance(s[1], str) else s)
                for s in leaves)
    d, h, v = 2048, 16, 102400
    attn = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    expert = 3 * d * 1408
    by_hand = (27 * (attn + 2 * d + 512) + 3 * d * 10944 + 26 * (d * 64 + 8 * expert + 2 * expert)
               + 2 * v * d + d)
    assert total == by_hand and round(total / 1e9, 2) == 3.11
    assert counts.kv_bytes_per_token(cfg) == 27 * 576 * 2 == 31104
    assert counts.attention_flops(cfg, 99) == 27 * 2 * 16 * (512 + 64 + 512) * 100
    n = 3 * d * 10944 + 27 * attn + 26 * (d * 64 + 2 * expert + 6 * 8 / 64 * expert) + d * v
    assert counts.matmul_params(cfg) == pytest.approx(n)
    # one token reads k * G / E = 0.75 held experts' worth on average; a
    # large step reads all 8
    one = counts.decode_bytes(cfg, [0]) - 31104
    everything = counts.weight_bytes(cfg)
    assert everything - one == pytest.approx(26 * (8 - 8 * 6 / 64) * expert * 2)
    assert mla_moe.expected_experts(cfg, 10 ** 6) == pytest.approx(8)
    # the program's configuration is what the file describes
    sys.path.insert(0, str(tiny.REPO / "src"))
    from repro.configs import get_config
    prog = get_config(cfg["arch"])
    assert {k: getattr(prog, k) for k in mla_moe.program_fields(cfg)} == mla_moe.program_fields(cfg)
