# Backend-unified synchronization API (the paper's Section-5 library,
# tentpole of PR 2):
#
#   protocols.py — uniform Barrier/Mutex/Semaphore protocols + the
#                  deterministic *Plan timeline types every backend returns
#   backends.py  — registry of implementation substrates: host (threading,
#                  observed-execution plans), kernel (Pallas, compiled on TPU,
#                  interpreted elsewhere), ref (pure-jnp oracles)
#   library.py   — SyncLibrary: machine abstraction -> (backend, algorithm,
#                  wait-strategy) triple, live constructors + plan() forms,
#                  cached host classification
#   window.py    — WindowedPlanner: shared power-of-2 bucketed fixed-window
#                  retrace avoidance for all three kernel families
#
# serve/, launch/, and benchmarks/ consume primitives exclusively through
# an injected SyncLibrary; core/api.py is a deprecation shim onto this
# package. See DESIGN.md §8.

from repro.sync.backends import (  # noqa: F401
    HostBackend,
    PallasBackend,
    SyncBackend,
    available_backends,
    get_backend,
    register_backend,
)
from repro.sync.library import (  # noqa: F401
    HOST_NOMINAL,
    SyncLibrary,
    SyncTimeoutError,
    classified_host,
)
from repro.sync.protocols import (  # noqa: F401
    Barrier,
    BarrierPlan,
    BoundedMutexPlan,
    Mutex,
    MutexPlan,
    Semaphore,
    SemaphorePlan,
)
from repro.sync.window import WindowedPlanner  # noqa: F401
