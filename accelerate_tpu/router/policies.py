"""Placement policies: pure functions of candidate snapshots.

A policy never touches replicas — the router builds a candidate list
(live, non-draining replicas with their cached
:class:`~accelerate_tpu.router.replica.ReplicaSnapshot` and, when the
policy wants it, the request's cached-chain overlap) and the policy
picks one. Keeping the policies pure makes them individually testable
on fake snapshots and individually comparable on the same trace.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from .replica import ReplicaSnapshot


@dataclasses.dataclass
class Candidate:
    """One routable replica as the policy sees it."""

    name: str
    order: int                 # registration order (the RR/tie-break axis)
    snapshot: ReplicaSnapshot
    #: prompt tokens already cached on this replica (0 when the policy
    #: did not ask for overlap, or nothing matched)
    overlap_tokens: int = 0


def load_score(snap: ReplicaSnapshot) -> float:
    """Scalar load used for least-loaded ordering and the affinity
    penalty: queued requests dominate (each is a whole request of
    unstarted work), busy seats count one each, and pool utilization
    breaks ties between equally-seated replicas (a fuller pool is
    closer to admission-blocking)."""
    return (
        float(snap.queue_depth)
        + float(snap.slots_active)
        + float(snap.pool_utilization)
    )


class RoundRobinPolicy:
    """The baseline: cycle through candidates in registration order,
    ignoring load and cache state entirely."""

    name = "round_robin"
    needs_overlap = False

    def __init__(self):
        self._next = 0

    def choose(self, candidates: Sequence[Candidate]) -> Candidate:
        # pick the first candidate at/after the cursor in registration
        # order; dead/draining replicas are already filtered out, so the
        # cursor just skips their order slots
        pick = min(
            candidates,
            key=lambda c: ((c.order - self._next) % _span(candidates), c.order),
        )
        self._next = pick.order + 1
        return pick


def _span(candidates: Sequence[Candidate]) -> int:
    return max(c.order for c in candidates) + 1


class LeastLoadedPolicy:
    """Route to the replica with the lowest :func:`load_score`;
    registration order breaks exact ties (deterministic placement for
    deterministic tests)."""

    name = "least_loaded"
    needs_overlap = False

    def choose(self, candidates: Sequence[Candidate]) -> Candidate:
        return min(
            candidates, key=lambda c: (load_score(c.snapshot), c.order)
        )


class PrefixAffinityPolicy:
    """Route on ``overlap_tokens − load_penalty × load_score``.

    ``overlap_tokens`` is the request's longest cached chain prefix on
    the candidate (computed host-side by the router from the replica's
    published key digest — block-granular, tenant-scoped). The penalty
    converts load into token units: ``load_penalty`` is "how many
    cached prefix tokens one unit of load is worth", so a replica with
    a deep queue must offer a proportionally longer warm prefix to win.
    With no overlap anywhere this degrades to exactly least-loaded —
    cold traffic spreads, templated cohorts concentrate.
    """

    name = "prefix_affinity"
    needs_overlap = True

    def __init__(self, load_penalty: float = 8.0):
        if load_penalty < 0:
            raise ValueError("load_penalty must be >= 0")
        self.load_penalty = load_penalty

    def choose(self, candidates: Sequence[Candidate]) -> Candidate:
        return max(
            candidates,
            key=lambda c: (
                c.overlap_tokens - self.load_penalty * load_score(c.snapshot),
                -load_score(c.snapshot),
                -c.order,
            ),
        )


_POLICIES = {
    "round_robin": RoundRobinPolicy,
    "least_loaded": LeastLoadedPolicy,
    "prefix_affinity": PrefixAffinityPolicy,
}


def make_policy(policy, load_penalty: Optional[float] = None):
    """Resolve a policy name (or pass an instance through). The string
    form is what callers use; ``load_penalty`` only applies to
    ``prefix_affinity``."""
    if not isinstance(policy, str):
        return policy
    try:
        cls = _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown routing policy {policy!r}; "
            f"want one of {sorted(_POLICIES)}"
        ) from None
    if cls is PrefixAffinityPolicy and load_penalty is not None:
        return cls(load_penalty=load_penalty)
    return cls()
