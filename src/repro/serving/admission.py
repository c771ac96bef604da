"""Device-resident admission for the serving engine (DESIGN.md §12).

:class:`DeviceAdmissionRing` wraps the fused CMP ring kernel
(:mod:`repro.kernels.cmp_ring`) for the engine's admission path: the
policy-drained batch is pushed into a bounded device ring and claim lanes are
filled in one fused device invocation — ring reclaim, batched enqueue, the
k-way earliest-cycle claim cascade and the frontier publish all happen
without a host sync in between (one device->host read per invocation returns
the claimed cycles).

Amortization works on both axes. Pushes batch naturally (enqueue-many is one
stage of the fused kernel). Claims amortize across engine steps via
*claim look-ahead*: one invocation claims up to ``claim_block >= k`` lanes
into a host-side FIFO buffer that subsequent steps serve without touching
the device — the claim cascade's fixed dispatch cost divides by
``claim_block``, the exact analogue of the host queue's batched
``dequeue_many``. Ring claims are earliest-cycle-first, so look-ahead
changes *when* claims commit, never their order.

The payload handle is the ring cycle number: the host keeps the authoritative
``cycle -> (QueueClass, Envelope)`` mirror, which is what makes checkpoints,
resizes and host failures exact — :meth:`flush` returns every ring-resident
entry (claim-buffered first, then unclaimed, both in cycle order) so callers
can requeue them at their original class seats before any fabric surgery.

Host-fallback rules (DESIGN.md §12): ``device_admission=True`` forces the
ring path (on CPU hosts the bit-identical jit'd oracle runs instead of the
Pallas kernel); ``"auto"`` enables it only when a TPU is attached; ``False``
keeps the pure host path.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import numpy as np

from repro.kernels import ops as kernel_ops


def resolve_device_admission(flag) -> bool:
    """Map a config flag (False | True | "auto") to an enable decision."""
    if flag == "auto":
        return kernel_ops.on_tpu()
    return bool(flag)


class DeviceAdmissionRing:
    """Bounded CMP ring on the accelerator feeding engine admission.

    Args:
      k: claim lanes the caller consumes per step (the engine's max_batch).
      claim_block: lanes claimed per fused invocation (the kernel's static
        cascade width); >= k enables claim look-ahead. Defaults to ``2*k``.
      capacity: ring slots. Sized so the steady state never rejects:
        non-FREE slots are bounded by unclaimed backlog + the claimed window,
        both well under capacity/2 for the engine's prefetch depth.
        Defaults to ``max(64, 2*claim_block)`` — the measured sweet spot
        (the oracle's cost grows with capacity, so oversizing the ring
        erodes the look-ahead amortization).
      window: protection window W for ring-slot recycling (paper Alg 4);
        defaults to capacity // 4.
      use_pallas: force the Pallas kernel (True) or the jit'd oracle (False);
        None picks by platform (Pallas on TPU). Resolved once, here, so
        ``use_pallas`` always says which path the ring runs.
    """

    def __init__(self, *, k: int, claim_block: int = 0, capacity: int = 0,
                 window: int = 0, use_pallas=None):
        self.k = int(k)
        self.claim_block = int(claim_block) if claim_block else 2 * self.k
        assert self.claim_block >= self.k
        self.capacity = int(capacity) if capacity else max(
            64, 2 * self.claim_block)
        self.window = int(window) if window else self.capacity // 4
        self.use_pallas = (kernel_ops.on_tpu() if use_pallas is None
                           else bool(use_pallas))
        self.state = np.zeros((self.capacity,), np.int32)
        self.cycle = np.zeros((self.capacity,), np.int32)
        self.meta = np.zeros((2,), np.int32)  # [enq_cycle, deque_cycle]
        self._enq = 0  # host mirror of meta[0]
        # Host mirror of the ring's unclaimed slots, FIFO by ring cycle —
        # claims always take the earliest cycles, so claimed entries leave
        # from the front and a dict keyed by cycle is never needed. Both
        # FIFOs are flat lists served by slicing (C-speed), the consumed
        # front dropped wholesale at each kernel call.
        self._mirror: List[Any] = []
        self._claimed: List[Any] = []  # look-ahead buffer, cycle order
        self._served = 0  # consumed front of _claimed
        self.stats = {"steps": 0, "kernel_calls": 0, "pushed": 0,
                      "claimed": 0, "rejected": 0}

    @classmethod
    def for_engine(cls, max_batch: int) -> "DeviceAdmissionRing":
        """The ring of an engine with ``max_batch`` lanes: claim look-ahead
        well past max_batch, since the fused invocation's fixed dispatch
        cost divides by claim_block, and the ordering relaxation it buys
        stays bounded by the prefetch depth."""
        return cls(k=max_batch, claim_block=8 * max_batch)

    # flight-recorder attachment (repro.obs): kernel calls and flushes are
    # already amortized/rare, so both are recorded unconditionally when a
    # MetricsHub has attached a recorder here.
    _obs = None

    @property
    def pending(self) -> int:
        """Entries resident in the admission path: unclaimed ring slots plus
        the claim look-ahead buffer (pushed, not yet handed to a lane)."""
        return len(self._mirror) + len(self._claimed) - self._served

    @property
    def buffered(self) -> int:
        """Claimed-ahead entries servable without a device invocation."""
        return len(self._claimed) - self._served

    @property
    def room(self) -> int:
        """How many pushes are guaranteed accepted next invocation
        (conservative: half the ring stays headroom for the
        claimed-but-windowed slots)."""
        return max(0, self.capacity // 2 - len(self._mirror))

    def step(self, entries: List[Any], want: int
             ) -> Tuple[List[Any], List[Any]]:
        """One engine admission step: push ``entries`` and take up to
        ``want`` claimed lanes. Serves from the look-ahead buffer when it
        can; otherwise ONE fused device invocation pushes the entries and
        claims the next ``claim_block`` earliest cycles. Returns
        ``(claimed, rejected)`` — claimed entries in exact ring-cycle (FIFO)
        order, rejected entries (ring full; rare by construction) for the
        caller to requeue on the host."""
        self.stats["steps"] += 1
        rejected: List[Any] = []
        if entries or (self.buffered < want and self._mirror):
            self._claimed = self._claimed[self._served:]  # drop served front
            self._served = 0
            req = np.asarray([len(entries), self.claim_block], np.int32)
            self.state, self.cycle, self.meta, claimed = kernel_ops.ring_step(
                self.state, self.cycle, self.meta, req,
                k=self.claim_block, window=self.window,
                use_pallas=self.use_pallas)
            # single host sync per invocation: new meta + claimed cycles
            meta_np, claimed_np = jax.device_get((self.meta, claimed))
            accepted = int(meta_np[0]) - self._enq
            self._enq = int(meta_np[0])
            if accepted:
                self._mirror.extend(entries[:accepted])
            # the kernel claims the n earliest cycles = the mirror's first n
            n_claimed = int((claimed_np >= 0).sum())
            self._claimed.extend(self._mirror[:n_claimed])
            del self._mirror[:n_claimed]
            self.stats["kernel_calls"] += 1
            self.stats["pushed"] += accepted
            self.stats["rejected"] += len(entries) - accepted
            rejected = list(entries[accepted:])
            if self._obs is not None:
                self._obs.emit("claim_block", "_ring", self._enq,
                               arg={"pushed": accepted,
                                    "claimed": n_claimed})
        lo = self._served
        hi = min(lo + want, len(self._claimed))
        out = self._claimed[lo:hi]
        self._served = hi
        self.stats["claimed"] += len(out)
        return out, rejected

    def flush(self) -> List[Any]:
        """Return every ring-resident entry in exact cycle order — the claim
        look-ahead buffer first (its cycles precede every unclaimed slot's),
        then the unclaimed mirror — and reset the slot states (cycle
        counters stay monotone). The checkpoint / resize / fail-host
        boundary: callers requeue the returned entries at their original
        class seats, so no seat is lost or reordered."""
        out = self._claimed[self._served:]
        out.extend(self._mirror)
        self._claimed = []
        self._served = 0
        self._mirror = []
        self.state = np.zeros_like(self.state)
        self.meta = np.asarray([self._enq, self._enq], np.int32)
        if self._obs is not None:
            self._obs.emit("flush", "_ring", self._enq, arg=len(out))
        return out
