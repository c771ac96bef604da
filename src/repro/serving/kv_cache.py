"""Paged KV cache on the CMP slot pool.

Pages are the queue nodes of the paper, transplanted (DESIGN.md §2) — the
third embodiment of the unified protection domain
(:mod:`repro.core.domain`):

  * a page is produced (allocated) with a monotone cycle — type-stable pool,
    never freed, only recycled;
  * a finishing/preempted request *retires* its pages (AVAILABLE->CLAIMED);
  * the engine's step counter is the cycle clock: each step unilaterally
    publishes ``deque_cycle = step`` (monotone, no coordination), and retired
    pages are reclaimed only when ``retire_cycle < step - W``
    (``domain.reclaim_retired_mask``) — so any decode step, DMA, or
    cross-host read launched in the last W steps can never see a recycled
    page (bounded-window UAF/ABA safety instead of refcounts).

Replaces: reference-counted block pools (vLLM-style) which need atomic
refcount traffic per block per step and stop-the-world compaction.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import slotpool as sp
from repro.core.domain import (
    AVAILABLE,
    CLAIMED,
    FREE,
    compute_window,
    reclaim_retired_mask,
    safe_cycle,
)


LANE = 128


def _tiles(width: int) -> int:
    return -(-width // LANE) * LANE


def page_widths(cfg: ModelConfig) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """(heads, width) of the k and the v stream of a page row, padded to
    whole 128-lane tiles (zeros in the padding). XLA stores a pool whose
    rows fill whole tiles page by page, so every layer of it is read where
    it lies; a row of any other width is stored with the page index minor
    and relayouted on every read. GQA: keys and values of each KV head,
    ``head_dim`` rounded up (phi3-mini's 96 -> 128). Latent attention
    (MLA): one head, the k stream holding the normed latent c at
    ``kv_lora_rank`` and the v stream the rotary key rounded up: 512 + 128
    lanes for the 576 values DeepSeek-V2 keeps."""
    if cfg.is_mla:
        return (1, cfg.kv_lora_rank), (1, _tiles(cfg.qk_rope_head_dim))
    kv, hd = cfg.num_kv_heads, _tiles(cfg.resolved_head_dim)
    return (kv, hd), (kv, hd)


class PagedKVPool:
    def __init__(self, cfg: ModelConfig, *, num_pages: int, page_size: int,
                 window: Optional[int] = None, dtype=None,
                 steps_per_sec: float = 100.0, resilience_s: float = 0.1):
        self.cfg = cfg
        self.page_size = page_size
        self.num_pages = num_pages
        # Window sizing is the domain formula W = max(MIN_WINDOW, OPS x R)
        # with OPS = decode steps/s and R = max request-preemption latency
        # before its blocks may be recycled (DESIGN.md §2).
        self.window = int(window) if window is not None else compute_window(
            steps_per_sec, resilience_s)
        r = cfg.pattern_repeats
        n_attn = sum(1 for k in cfg.block_pattern if k in ("dense", "moe", "hymba"))
        self.layers = cfg.first_dense_layers + r * n_attn
        dt = dtype or jnp.dtype(cfg.dtype)
        (kv, dk), (_, dv) = page_widths(cfg)
        # [L, P, KV, page, d] — stacked over attention layers
        self.k_pages = jnp.zeros((self.layers, num_pages, kv, page_size, dk), dt)
        self.v_pages = jnp.zeros((self.layers, num_pages, kv, page_size, dv), dt)
        self.pool = sp.make(num_pages)
        # Optional tenant quota ledger (duck-typed: charge/credit, see
        # repro.sched.tenants.TenantQuotaLedger — kept out of the
        # constructor so engines stay ledger-agnostic). When attached,
        # alloc_for/retire_for meter per-tenant page occupancy against it;
        # the plain alloc/retire paths are untouched.
        self.ledger = None

    def attach_ledger(self, ledger, host: int = 0) -> None:
        """Attach a per-tenant page-quota ledger (any object with
        ``charge(tenant, host, pages) -> bool`` /
        ``credit(tenant, host, pages)``). Engine code keeps calling
        ``alloc``/``retire``; tenant-aware callers use
        ``alloc_for``/``retire_for`` instead."""
        self.ledger = ledger
        self._ledger_host = int(host)

    # ------------------------------------------------------------------
    def tick(self, step: int) -> None:
        """Unilateral monotone boundary publish + window reclamation."""
        self.pool = sp.advance(self.pool, jnp.int32(step))
        self.pool, _ = sp.reclaim_retired(self.pool, self.window)

    def alloc(self, n: int) -> Tuple[jax.Array, jax.Array]:
        """Allocate n pages (FREE -> AVAILABLE/live). Returns (ids, valid)."""
        self.pool, ids, valid = sp.produce_with_reclaim(self.pool, n, self.window)
        return ids, valid

    def retire(self, ids: jax.Array) -> None:
        """Request done/preempted: pages become reclamation candidates after
        the window elapses. Never blocks; never coordinates."""
        valid = ids < self.num_pages
        self.pool = sp.claim_ids(self.pool, ids, valid)

    # ---------------------------------------------------- tenant metering
    def alloc_for(self, tenant, n: int) -> Tuple[jax.Array, jax.Array]:
        """Tenant-metered ``alloc``: charge the attached ledger before
        touching the pool, so a tenant over quota is denied without
        consuming a produce cycle. Denials return (empty, empty) — the
        same shape callers already handle for a dry pool. Without a
        ledger this is exactly ``alloc``."""
        if self.ledger is not None and n > 0:
            if not self.ledger.charge(tenant, self._ledger_host, n):
                empty = jnp.zeros((0,), jnp.int32)
                return empty, empty
        ids, valid = self.alloc(n)
        if self.ledger is not None and n > 0:
            granted = int(jnp.sum(valid))
            if granted < n:  # pool dry: give back the unfilled estimate
                self.ledger.credit(tenant, self._ledger_host, n - granted)
        return ids, valid

    def retire_for(self, tenant, ids: jax.Array) -> None:
        """Tenant-metered ``retire``: credit the ledger for every page
        actually returned. Without a ledger this is exactly ``retire``."""
        pages = int(jnp.sum(ids < self.num_pages))
        self.retire(ids)
        if self.ledger is not None and pages > 0:
            self.ledger.credit(tenant, self._ledger_host, pages)

    # ------------------------------------------------------------------
    def free_pages(self) -> int:
        return int(jnp.sum(self.pool.state == FREE))

    def live_pages(self) -> int:
        return int(jnp.sum((self.pool.state == AVAILABLE)
                           | (self.pool.state == CLAIMED)))

    def reclaimable_pages(self) -> int:
        """Pages whose retire cycle fell behind the window — exactly the
        domain predicate the next ``tick`` will recycle."""
        return int(jnp.sum(reclaim_retired_mask(
            self.pool.state, self.pool.retire_cycle,
            self.pool.deque_cycle, self.window)))

    def protection_boundary(self) -> int:
        """Current safe cycle max(0, deque_cycle - W) (diagnostics)."""
        return int(safe_cycle(int(self.pool.deque_cycle), self.window))
