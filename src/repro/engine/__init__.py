"""Execution-plan layer: one resolved `Plan` threaded through every stage.

``Plan`` freezes the execution decisions — kernel backend, optional device
mesh + sharded axis, and every chunk/tile size — ONCE, at the front door
(``repro.api.MultiHDBSCAN`` or ``core.multi.fit_msts``), so the pipeline
stages are pure compositions that never re-derive "where am I running".
``resolve_plan`` mirrors the ``dist.sharding.resolve_rules`` philosophy:
requested placement is filtered against the hardware that actually exists,
so the same user code runs on a laptop (mesh ignored / trivial) and a pod.

``io`` holds the device->host choke point: every bulk materialization in the
pipeline goes through ``to_host``, which a test ledger can count (and, under
``transfer_ledger``, a jax transfer guard turns any *implicit* device->host
sync into an error).  ``trace`` holds the host spans and counters that name
each stage's host time, on the profiler's clock.
"""

from . import io, plan, trace
from .io import to_host, transfer_ledger
from .plan import Plan, cached_program, resolve_plan

__all__ = [
    "Plan", "cached_program", "io", "plan", "resolve_plan", "to_host",
    "trace", "transfer_ledger",
]
