"""Bring-up check on one TPU: full-width yi-6b served through the fabric.

    python chip_smoke.py

Drives the path a user drives -- ``Fabric.open`` -> ``submit`` -> ``step``
until idle -- on the published yi-6b shape (32 layers, d_model 4096, GQA
32/4, d_ff 11008, vocab 64000, bf16) with random weights from
``PARAM_SEED``. Two serving runs share one set of params, one after the
other, each fabric closed before the next opens: first the host policy
drain, then the device admission ring (the Pallas kernel). Both must
complete every request, with token-identical outputs. Then the paged
prefill logits of one prompt are compared with the plain dense forward of
``repro.models.model``, both in float32 at matmul precision "highest".

Exits non-zero, printing no result, when JAX's default device is not a TPU,
when the ``repro`` package is not in ``src/`` beside this script, or when
any phase fails. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

ARCH = "yi-6b"
PARAM_SEED = 0
PROMPT_SEED = 1
# Serving geometry: params (11.29 GiB) + the KV pool (1.0 GiB) + the
# second pool each forward call writes (the pool is not donated) + the
# step's temporaries fit the chip's 16 GiB; tests/test_chip_compile.py
# checks this geometry against the chip's compiler.
MAX_BATCH = 8
PAGE_SIZE = 16
NUM_PAGES = 1024
MAX_SEQ = 256
MAX_NEW = 8
# 10 requests over 3 distinct prompt lengths (each length is one prefill
# compile), so two waves of lanes and a refill mid-run.
PROMPT_LENS = (7, 16, 29, 7, 16, 29, 7, 16, 29, 7)
# Paged vs dense float32 logits: max |diff| over max |dense|. Both run the
# same bf16 weights in float32 at "highest" precision and differ only in
# reduction order (paged gather vs dense attention), about 1e-5 of the
# logit scale after 32 layers; a wrong page, mask or position moves the
# logits by O(1) of their scale.
LOGITS_RTOL = 1e-3


def _require(ok, what: str) -> None:
    """A phase check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"chip_smoke failed: {what}")


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _peak_gib(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 2**30


def _prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(PROMPT_SEED)
    return [rng.integers(1, vocab, size=n).tolist() for n in PROMPT_LENS]


def _serve(label: str, device_admission: bool, prompts, params, dev):
    """One serving run through the fabric; returns (outputs, params)."""
    from repro.fabric import Fabric, FabricConfig

    config = FabricConfig(arch=ARCH, smoke=False, param_seed=PARAM_SEED,
                          max_batch=MAX_BATCH, page_size=PAGE_SIZE,
                          num_pages=NUM_PAGES, max_seq=MAX_SEQ,
                          device_admission=device_admission)
    t0 = time.perf_counter()
    fab = Fabric.open(config, params=params)
    cfg = fab.model_cfg
    uids = [fab.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    _require(None not in uids, f"{label}: a submit was rejected: {uids}")
    steps = 0
    while not fab.idle():
        fab.step()
        steps += 1
        _require(steps <= 50 * len(prompts), f"{label}: not idle after {steps}")
    outputs = {u: list(fab.completed[u].output) for u in uids}
    for u, out in outputs.items():
        _require(len(out) == MAX_NEW
                 and all(0 <= t < cfg.vocab_size for t in out),
                 f"{label}: request {u} gave {out}")
    line = (f"{label}: completed {len(outputs)}/{len(uids)} requests in "
            f"{steps} fabric steps, wall {time.perf_counter() - t0:.1f}s "
            f"incl. compile")
    (eng,) = fab.engines
    ring = eng.admission_ring
    if device_admission:
        _require(ring is not None and ring.use_pallas,
                 f"{label}: the ring is not on the Pallas kernel")
        _require(ring.stats["kernel_calls"] > 0, f"{label}: {ring.stats}")
        line += (f"; ring pallas={ring.use_pallas} "
                 f"kernel_calls={ring.stats['kernel_calls']} "
                 f"claimed={ring.stats['claimed']}")
    else:
        _require(ring is None, f"{label}: expected the host drain")
    _log(line + f"; peak {_peak_gib(dev):.2f} GiB")
    params = fab.params
    fab.close()
    del fab, eng, ring
    gc.collect()  # the fabric holds cycles: free its KV pool before the next
    return outputs, params


def _logits_gap(params, prompt):
    """Paged prefill vs dense forward, both float32 on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.paged_model import paged_forward

    cfg = get_config(ARCH)
    toks = jnp.asarray([prompt], jnp.int32)
    n_pages = -(-len(prompt) // PAGE_SIZE)

    def f32(p):
        # float32 embeddings make every activation float32; the bf16
        # layer weights are promoted inside each matmul, one layer at a
        # time, so no float32 copy of the whole model is made.
        return {**p, "embed": p["embed"].astype(jnp.float32)}

    def paged(p, t):
        shape = (cfg.num_layers, n_pages + 1, cfg.num_kv_heads, PAGE_SIZE,
                 cfg.resolved_head_dim)
        pool = jnp.zeros(shape, jnp.float32)
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        logits, _, _ = paged_forward(f32(p), t, cfg, pool, pool, table,
                                     jnp.zeros((1,), jnp.int32))
        return logits

    def dense(p, t):
        logits, _ = M.apply(f32(p), t, cfg)
        return logits[:, -1]

    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(paged)(params, toks))
        ref = np.asarray(jax.jit(dense)(params, toks))
    _require(got.shape == ref.shape == (1, cfg.vocab_size),
             f"logits shapes {got.shape} vs {ref.shape}")
    _require(np.isfinite(got).all() and np.isfinite(ref).all(),
             "non-finite logits")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind}); this check runs only "
              f"on a TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
         f"{enable_compile_cache()}")
    cfg = get_config(ARCH)
    _log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
         f"heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, "
         f"vocab {cfg.vocab_size}, {cfg.dtype}; pool {NUM_PAGES} pages x "
         f"{PAGE_SIZE} tokens, max_batch {MAX_BATCH}")
    prompts = _prompts(cfg.vocab_size)

    host, params = _serve("host drain", False, prompts, None, dev)
    ring, _ = _serve("device ring", True, prompts, params, dev)
    _require(ring == host, "ring and host-drain outputs differ: " + str(
        {u: (host[u], ring[u]) for u in host if host[u] != ring.get(u)}))
    _log(f"ring == host: {len(host)} requests token-identical")

    gap = _logits_gap(params, max(prompts, key=len))
    _log(f"paged vs dense float32 logits: max|diff|/max|ref| = {gap:.3e} "
         f"(limit {LOGITS_RTOL:.0e})")
    _require(gap <= LOGITS_RTOL, f"logits gap {gap} > {LOGITS_RTOL}")
    _log(f"peak device memory {_peak_gib(dev):.2f} GiB of "
         f"{dev.memory_stats()['bytes_limit'] / 2**30:.2f} GiB")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
