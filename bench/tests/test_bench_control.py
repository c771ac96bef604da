"""The control of ``max_logit_gap`` at a size a test run holds: the plain
reference rounded to int8 or to fp8 (the control of the chip's cells), put
in the program's place at each served position, reads above the limit on
every seed, while the program (float32 at this size) reads under it; on the
dense and on the MoE tiny cell."""

import json
import sys

import pytest

from bench import check, generator, run
from bench.tests import tiny

LIMIT = 1e-4  # the tiny cells' limit (tiny.make_root)


def serve_seeds(tmp_path_factory, cell):
    """Seeds 1, 2 and 3 of ``cell`` served: (cfg, traffic, run record,
    weights) per seed."""
    root = tiny.make_root(tmp_path_factory.mktemp("checkout"), limit=LIMIT)
    if str(tiny.REPO / "src") not in sys.path:
        sys.path.insert(0, str(tiny.REPO / "src"))
    _, _, cfg, traffic, _ = run.spec.load_cell(root, cell)
    run.count_compiles()
    dev = run.device_info(1, {}, require_tpu=False)
    run.cover(cfg, traffic)
    out = {}
    for seed in (1, 2, 3):
        rec, weights = run.serve(cfg, traffic, seed, 2.0, False, {"devices": {}}, dict(dev))
        out[seed] = (cfg, traffic, rec, weights)
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return serve_seeds(tmp_path_factory, "tiny.open")


@pytest.fixture(scope="module")
def served_moe(tmp_path_factory):
    return serve_seeds(tmp_path_factory, "tiny.moe.open")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(served, seed):
    cfg, traffic, rec, weights = served[seed]
    sample = [t for t in rec["tracks"] if t.output]
    length = check.padded_length(traffic)
    program = max(check.widest_gaps(cfg, weights, sample, length))
    control = max(check.widest_gaps(cfg, weights, sample, length, "int8"))
    assert program <= LIMIT < control


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_where_the_program_passes(served, seed):
    cfg, traffic, rec, weights = served[seed]
    sample = [t for t in rec["tracks"] if t.output]
    length = check.padded_length(traffic)
    program = max(check.widest_gaps(cfg, weights, sample, length))
    control = max(check.widest_gaps(cfg, weights, sample, length, "fp8"))
    assert program <= LIMIT < control


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_moe_control_fails_where_the_program_passes(served_moe, seed, quant):
    """The same on the MoE cell: its reference rounded, router included."""
    cfg, traffic, rec, weights = served_moe[seed]
    sample = [t for t in rec["tracks"] if t.output]
    length = check.padded_length(traffic)
    program = max(check.widest_gaps(cfg, weights, sample, length))
    control = max(check.widest_gaps(cfg, weights, sample, length, quant))
    assert program <= LIMIT < control


def test_reference_agrees_with_its_own_greedy_decode():
    """The reference's gap is 0 on tokens it chose itself."""
    import numpy as np

    from bench import weights as W
    from bench.configs import dense_reference as R
    cfg = json.loads((tiny.DATA / "tiny_yi.json").read_text())
    w = W.make(cfg, 4)
    prompt = generator.rng_for(4, 0).integers(1, cfg["vocab_size"], size=20).tolist()
    toks = list(prompt)
    for _ in range(6):
        toks.append(int(np.argmax(np.asarray(R.logits(cfg, w, np.array(toks, np.int32)))[-1])))
    req = generator.Request(0, np.array(prompt, np.int32), 6, 0.0)
    track = run.loop.Track(req, 0.0, 0.0, uid=0, output=toks[len(prompt):])
    assert check.widest_gaps(cfg, w, [track], 128) == [0.0]
