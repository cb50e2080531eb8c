"""Device->host materialization choke point + transfer accounting.

The pipeline contract (docs/architecture.md "Device / host boundaries") is
that bulk device->host syncs happen ONLY at named materialization points:

  ``knn``             — the kNN stage's host view (stored on the result object
                        and consumed by the WSPD control plane).
  ``candidate_count`` — a handful of scalars sizing the static candidate
                        buffers: on the fused-cascade path the (slot, unique,
                        mutual, tie-overflow) counts in ONE sync; on the
                        slot-array path the unique candidate count.
  ``candidate_slots`` — slot-array path only: ONE scalar, the real
                        (non-sentinel) SBCN slot count sizing the scatter
                        compaction ahead of the dedup sort.
  ``stage1_count``    — fused path only: the (certified, open) stage-1
                        survivor counts in ONE sync, sizing the stage-2
                        compactions.
  ``graph``           — RNG^kmax filter-verdict + edge compaction.
  ``lune_exact``      — variant="rng" only: the unresolved-edge subset for the
                        exact lune scan.
  ``mst``             — the final MST compaction, the single sync of the MST
                        stage.
  ``linkage``         — the batched single-linkage merge arrays (left, right,
                        height, size) for a whole mpts sweep in ONE sync
                        (core.multi.linkage_range).
  ``predict``         — the out-of-sample path's single sync: per-row
                        attachment lambdas + neighbours for a query batch
                        (core.predict; the condensed-tree walk is host work).
  ``candidates``      — the SBCN edge list's host view (stored on the result
                        object for inspection/debugging; not on the hot path).
  ``input``           — ``ensure_host`` normalizing device arrays handed to a
                        host-facing entry point.
  ``lm_decode``       — the LM serving demo's per-step token sync (the decode
                        loop is inherently host-driven; out of the clustering
                        pipeline's budget).

Each ``to_host`` call is also the span ``to_host.<tag>`` (``engine.trace``):
the host waiting on the device and copying, on the profiler's clock.

Everything else stays device-resident.  ``transfer_ledger`` is the test hook
that enforces this: inside the context every ``to_host`` call is recorded as
``(tag, nbytes)`` and jax's transfer guard turns any *implicit* device->host
transfer (e.g. a stray ``np.asarray`` on a jax array) into an error.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np

from . import trace

_LEDGER = threading.local()


def _nbytes(tree) -> int:
    return sum(
        getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)
    )


def to_host(tree, tag: str):
    """Explicitly materialize a pytree of device arrays as numpy, ledgered.

    This is the ONLY sanctioned device->host transfer in the clustering
    pipeline; ``tag`` names the materialization point (see module docstring).
    """
    with trace.span(trace.SYNC_PREFIX + tag):
        out = jax.device_get(tree)
    ledger = getattr(_LEDGER, "value", None)
    if ledger is not None:
        ledger.append((tag, _nbytes(out)))
    return out


@contextlib.contextmanager
def transfer_ledger(*, guard: bool = True):
    """Record every ``to_host`` as (tag, nbytes); optionally guard implicits.

    With ``guard=True`` (default) the context also arms
    ``jax.transfer_guard_device_to_host("disallow")``, which errors on any
    implicit device->host transfer while leaving the explicit
    ``jax.device_get`` inside ``to_host`` allowed — so the ledger provably
    sees *all* syncs, not just the polite ones.
    """
    prev = getattr(_LEDGER, "value", None)
    ledger: list[tuple[str, int]] = []
    _LEDGER.value = ledger
    try:
        if guard:
            with jax.transfer_guard_device_to_host("disallow"):
                yield ledger
        else:
            yield ledger
    finally:
        _LEDGER.value = prev


def tags(ledger) -> list[str]:
    """The sequence of materialization tags a ledger recorded."""
    return [t for t, _ in ledger]


def count(ledger, tag: str) -> int:
    """How many materializations a ledger recorded under ``tag``."""
    return sum(1 for t, _ in ledger if t == tag)


def ensure_host(x) -> np.ndarray:
    """Host view of ``x`` without triggering the transfer guard for numpy.

    numpy inputs pass through untouched; jax arrays go through ``to_host``
    under the ``input`` tag (only hit when a caller hands device arrays to a
    host-facing entry point).
    """
    if isinstance(x, np.ndarray):
        return x
    if hasattr(x, "__array_namespace__") or type(x).__module__.startswith("jax"):
        return to_host(x, "input")
    return np.asarray(x)
