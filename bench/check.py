"""What decides ``correct``: the served tokens against the plain reference,
and admission (every request due is served once, with its length).

The served-token comparison takes a sample, drawn from the seed, of the
requests the run finished, the longest among them, and runs the
configuration's reference over each prompt followed by its served tokens.
At each served position it reads the gap by which the served token's
reference logit lies below the reference's best logit there; the number
compared is the widest gap over the sample. The control reads, at the same
positions, the gap of the token that a lower-precision reference puts
first.
"""

from __future__ import annotations

import functools
import importlib
from typing import List, Optional

import numpy as np

from bench import generator

#: the sample: at least this many requests and served tokens, at most
#: MAX_REQUESTS requests
MIN_REQUESTS, MIN_TOKENS, MAX_REQUESTS = 4, 400, 8


def sample(tracks, seed: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds MIN_REQUESTS and MIN_TOKENS."""
    done = [t for t in tracks if t.output]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.prompt) + len(t.output), -t.req.index))
    rest = [t for t in done if t is not longest]
    order = generator.rng_for(seed, 11).permutation(len(rest))
    out, tokens = [longest], len(longest.output)
    for i in order:
        if len(out) >= MAX_REQUESTS or (len(out) >= MIN_REQUESTS and tokens >= MIN_TOKENS):
            break
        out.append(rest[i])
        tokens += len(rest[i].output)
    return out


def padded_length(traffic: dict) -> int:
    """One reference length per cell (one compile): the longest prompt and
    output the traffic can send, rounded up to 256."""
    longest = (max(generator.length_values(traffic["prompt_len"]))
               + max(generator.length_values(traffic["output_len"])))
    return -(-longest // 256) * 256


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.configs.{cfg['reference']}")


@functools.lru_cache(maxsize=None)
def _gap_fn(ref_name: str, quant: Optional[str]):
    import jax
    import jax.numpy as jnp
    ref = importlib.import_module(f"bench.configs.{ref_name}")

    def gaps(cfg_items, weights, tokens, targets):
        cfg = dict(cfg_items)
        exact = ref.logits(cfg, weights, tokens)
        best = jnp.max(exact, -1)
        if quant is None:
            pick = jnp.clip(targets, 0, exact.shape[-1] - 1)
        else:
            pick = jnp.argmax(ref.logits(cfg, weights, tokens, quant), -1)
        gap = best - jnp.take_along_axis(exact, pick[:, None], -1)[:, 0]
        return jnp.where(targets >= 0, gap, 0.0)

    return jax.jit(gaps, static_argnums=0)


def _hashable(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def widest_gaps(cfg: dict, weights, tracks, length: int,
                quant: Optional[str] = None) -> List[float]:
    """Per sampled request, the widest gap over its served tokens (with
    ``quant``, the control's gaps at the same positions)."""
    fn = _gap_fn(cfg["reference"], quant)
    out = []
    for t in tracks:
        prompt, served = list(t.req.prompt), list(t.output)
        seq = prompt + served[:-1]
        if len(seq) > length:
            raise ValueError(f"request of {len(seq)} tokens exceeds the reference length {length}")
        tokens = np.zeros(length, np.int32)
        tokens[:len(seq)] = seq
        targets = np.full(length, -1, np.int32)
        targets[len(prompt) - 1:len(prompt) - 1 + len(served)] = served
        out.append(float(np.max(np.asarray(fn(_hashable(cfg), weights, tokens, targets)))))
    return out


def admission(tracks, loop: str, in_flight: int) -> dict:
    """Counts that must all be 0: requests due and never served (a request
    refused at submit among them), served more than once, or served with
    another length than asked. In a closed loop the requests the fabric
    still holds at the end (``in_flight``) are not unserved; any other
    request not done is."""
    accepted = [t for t in tracks if t.uid is not None]
    refused = len(tracks) - len(accepted)
    not_done = sum(1 for t in accepted if t.done is None)
    return {
        "unserved": refused + (not_done if loop == "open" else max(0, not_done - in_flight)),
        "served_twice": sum(1 for t in accepted if t.times_done > 1),
        "wrong_length": sum(1 for t in accepted
                            if t.output is not None and len(t.output) != t.req.max_new),
    }
