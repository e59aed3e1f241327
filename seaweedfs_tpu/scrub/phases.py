"""The phases of a scrub pass, each under one clock.

scan (needle sweep of a normal volume), scan_ec (needle sweep of an EC
volume over its local shards: in a full pass twice a volume, its .ecx
walk before the verify and, after it, the needles the verify's staged
bytes did not settle), verify (the one fused stripe verify of a pass,
with the needle checks in its staged bytes on its writer lanes), repair (quarantine + rebuild of one volume's condemned shards),
reverify (the stripe verify that follows a repair): always observed
into SeaweedFS_scrub_phase_seconds{phase}, a span scrub.<phase> only
while the span ring is on.
"""

from __future__ import annotations

from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.stats.metrics import ScrubPhaseSecondsHistogram

# children resolved once at import: labels() takes a lock per call
_PHASE_HIST = {p: ScrubPhaseSecondsHistogram.labels(p)
               for p in ("scan", "scan_ec", "verify", "repair", "reverify")}


def phase(name: str, **tags) -> trace.PhaseTimer:
    return trace.PhaseTimer(_PHASE_HIST[name], "scrub." + name, **tags)
