"""Background integrity scrub & self-repair.

SeaweedFS trusts bytes once written: needle CRCs are checked on reads,
EC shards never again after encode — the latent-sector-error failure
mode warm stores guard against with continuous scrubbing (f4-style).
This package closes that gap with three parts:

  scanner   walks mounted volumes and EC volumes at a throttled pace,
            recomputing needle CRCs and re-encoding EC data shards on
            the fleet scheduler's staged loop (ec/fleet.py): the
            readers fill reused [14, lanes] staging buffers — the data
            shards and the stored parity — and the jax backend compares
            on the device (counts come back, not parity); host codecs
            compare on the writer lanes.
  planner   classifies damage (bad parity shard vs bad data shard vs
            unrecoverable), quarantines corrupt files with a .corrupt
            rename, and reconstructs shards via the fleet rebuild path
            (needles come back from replicas).
  daemon    the control plane: a background thread per volume server
            with start/pause/status, wired to VolumeScrubStart/Pause/
            Status RPCs, the HTTP /status page, the master's staggered
            scheduler, and the `volume.scrub` shell command, whose
            `-wait` returns when the pass has ended, with the pass's
            verdict on every volume it covered.

Everything is instrumented with the PR 2 primitives: the span
scrub.pass, the phases scan / scan_ec / verify / repair / reverify
(SeaweedFS_scrub_phase_seconds always, spans scrub.<phase> while the
ring is on) and the SeaweedFS_scrub_* metric families.
"""

from seaweedfs_tpu.scrub.daemon import ScrubDaemon, PassResult
from seaweedfs_tpu.scrub.planner import (EcDamage, classify_ec_damage,
                                         repair_ec_volume, repair_needle)
from seaweedfs_tpu.scrub.scanner import (EcNeedleScan, NeedleScan,
                                         scan_ec_volume_needles,
                                         scan_volume)

__all__ = [
    "ScrubDaemon", "PassResult",
    "EcDamage", "classify_ec_damage", "repair_ec_volume", "repair_needle",
    "EcNeedleScan", "NeedleScan", "scan_ec_volume_needles", "scan_volume",
]
