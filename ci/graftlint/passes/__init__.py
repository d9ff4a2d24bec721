"""Pass registry — every pass, in the order the runner executes them.

The five migrated syntactic passes first (cheapest), then the four
dataflow passes, then the opt-in orchestrated runner (excluded from
the default set; see its module docstring)."""

from __future__ import annotations

from .bare_except import BareExceptPass
from .collective_consistency import CollectiveConsistencyPass
from .donation import DonationPass
from .env_docs import EnvDocsPass
from .event_docs import EventDocsPass
from .host_sync import HostSyncPass
from .lock_discipline import LockDisciplinePass
from .orchestrated import CompileCachePass
from .print_call import PrintPass
from .recompile_hazard import RecompileHazardPass
from .replica_divergence import ReplicaDivergencePass
from .signal_restore import SignalRestorePass
from .spec_shape import SpecShapePass
from .state_protocol import StateProtocolPass
from .tracer_purity import TracerPurityPass

ALL_PASSES = (
    BareExceptPass,
    PrintPass,
    EnvDocsPass,
    EventDocsPass,
    HostSyncPass,
    SignalRestorePass,
    TracerPurityPass,
    RecompileHazardPass,
    DonationPass,
    LockDisciplinePass,
    CollectiveConsistencyPass,
    ReplicaDivergencePass,
    SpecShapePass,
    StateProtocolPass,
    CompileCachePass,
)

#: the default ``python -m ci.graftlint`` set: every source-analysis
#: pass; the orchestrated runner is opt-in by name
DEFAULT_PASSES = tuple(p for p in ALL_PASSES if not p.orchestrated)


def by_id(pass_id):
    for cls in ALL_PASSES:
        if cls.id == pass_id:
            return cls
    raise KeyError("unknown graftlint pass %r (known: %s)"
                   % (pass_id, ", ".join(c.id for c in ALL_PASSES)))
