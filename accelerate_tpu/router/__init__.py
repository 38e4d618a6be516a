"""Fleet serving: a host-side router over N ``ServingEngine`` replicas.

One engine became feature-rich (continuous batching, prefix cache,
speculation, capacity levers); this package turns it into a FLEET. The
router is pure host policy over the observability plane the engines
already export — live gauges (PR 8), content-addressed prefix chain
keys (PR 13), ``/healthz`` — so placement needs no new device code and
no engine changes beyond ``drain()`` and the bounded prefix digest.

Three composable placement policies (:mod:`.policies`):

* **round-robin** — the baseline every other policy is benchmarked
  against;
* **least-loaded** — admission from live replica gauges (queue depth,
  seat occupancy, pool utilization, tokens in flight), read directly
  from in-process replicas or scraped over HTTP, with
  staleness-tolerant cached snapshots (a dead scrape degrades to the
  last known posture — it never wedges admission);
* **prefix-affinity** — replicas publish a bounded digest of their
  cached chain keys; the router computes cached-chain overlap per
  candidate host-side and routes on ``overlap_tokens − load_penalty ×
  load``, so templated cohorts pile onto the replica that already
  holds their prefix instead of duplicating it N ways (the
  Mooncake/DistServe placement insight).

Session affinity rides on top of any base policy: bounded per-key
state, graceful spill when the pinned replica drains or dies.

Prefill/decode disaggregation (PR 19) builds on the same machinery:
``FleetRouter(placement="disagg")`` splits the fleet by engine role —
prompts land on the least-loaded prefill replica (prefix affinity
still applies), and each finished KV chain hands off to the decode
replica with the deepest cached-chain overlap through an in-flight
transfer ledger (per-request ``transfer_ms`` + bytes, block dedup
against the destination's CACHED index, re-queue on a dead endpoint,
``transfer_stall``/``transfer_drop`` chaos arms).

Everything is default-OFF: nothing in the single-engine path imports or
consults this package, and a :class:`FleetRouter` only exists where
user code builds one. The router is
duck-type compatible with :class:`~accelerate_tpu.loadgen.SoakHarness`'s
engine surface (``add_request`` / ``step`` / ``has_work`` / ...), so
the PR 16 soak harness drives a fleet unchanged.
"""

from .policies import (
    LeastLoadedPolicy,
    PrefixAffinityPolicy,
    RoundRobinPolicy,
    load_score,
    make_policy,
)
from .replica import HTTPReplica, InProcessReplica, ReplicaSnapshot
from .router import FleetRouter

__all__ = [
    "FleetRouter",
    "HTTPReplica",
    "InProcessReplica",
    "LeastLoadedPolicy",
    "PrefixAffinityPolicy",
    "ReplicaSnapshot",
    "RoundRobinPolicy",
    "load_score",
    "make_policy",
]
