"""Tuner-as-a-service: persistent daemon + content-addressed plan store.

``PlanStore`` (store.py) is the on-disk tier — tuned plans and per-cell
transposition-cache snapshots, atomic-published and quarantine-validated.
``TunerService``/``serve_forever`` (daemon.py) is the long-lived loop
sharing per-cell caches and one measurement fleet across runs.
CLI: ``python -m repro.launch.tune_serve``.
"""
from repro.service.daemon import TunerService, serve_forever
from repro.service.store import (
    PlanStore,
    canonical_request,
    cell_key,
    request_key,
)

__all__ = [
    "PlanStore",
    "TunerService",
    "canonical_request",
    "cell_key",
    "request_key",
    "serve_forever",
]
