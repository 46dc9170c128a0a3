"""Continuous-batching serving engine with tile-aligned bucketed KV caches.

Public surface:
  Engine                  — the serving loop (engine.py)
  Request / SamplingParams / Completion / EngineStats — request API
  FINISH_REASONS / OK_REASONS — the finish_reason catalog (request.py)
  BucketPolicy / make_policy — tile-aligned shape policy (buckets.py)
  BlockPool / PagedPool   — block-table KV pool with prefix caching + COW
                            (kv_pool.py)
  ShedPolicy / Shed       — admission control / overload shedding
  FaultPlan / chaos_soak  — deterministic fault injection (faults.py)
  synthetic_requests      — workload generator shared with benchmarks
"""
from .buckets import BucketPolicy, make_policy
from .engine import Engine
from .faults import FaultEvent, FaultPlan, SoakResult, chaos_soak
from .kv_pool import BlockPool, BlockSeq, CowCopy, PagedPool, PoolExhausted
from .request import (FINISH_REASONS, OK_REASONS, Completion, EngineStats,
                      Request, SamplingParams)
from .scheduler import RequestQueue, Scheduler, Shed, ShedPolicy
from .workload import PATTERNS, synthetic_requests

__all__ = [
    "Engine", "Request", "SamplingParams", "Completion", "EngineStats",
    "FINISH_REASONS", "OK_REASONS",
    "BucketPolicy", "make_policy", "BlockPool", "BlockSeq",
    "CowCopy", "PagedPool", "PoolExhausted", "RequestQueue", "Scheduler",
    "Shed", "ShedPolicy", "FaultEvent", "FaultPlan", "SoakResult",
    "chaos_soak", "PATTERNS", "synthetic_requests",
]
