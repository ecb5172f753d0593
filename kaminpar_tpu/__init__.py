"""kaminpar-tpu: a TPU-native balanced k-way graph partitioning framework.

Re-implements the capabilities of KaMinPar (deep multilevel graph
partitioning; see SURVEY.md) with a JAX/XLA/Pallas compute path: the hot
kernels — size-constrained label propagation, cluster contraction, LP/Jet
refinement, balancing — run as segmented sort/scatter array programs on a
device-resident CSR graph; sequential initial bipartitioning and the
multilevel orchestration run on the host; multi-chip scaling uses
jax.sharding meshes with XLA collectives instead of MPI.
"""

import time as _time

_IMPORT_START = _time.perf_counter()  # first line: the package's own import

from .graphs import (  # noqa: F401
    HostGraph,
    DeviceGraph,
    from_edge_list,
    from_csr,
    device_graph_from_host,
    host_graph_from_device,
    validate,
)
from .io import load_graph  # noqa: F401
from . import telemetry  # noqa: F401
from .context import Context  # noqa: F401
from .presets import create_context_by_preset_name, get_preset_names  # noqa: F401
from .kaminpar import KaMinPar, context_from_preset  # noqa: F401

__version__ = "0.1.0"

# set-up on the program's own account, telemetry on or off: the
# jax.monitoring listeners, and what the lines above took (modules
# imported lazily later are not in it; jax's own import is, unless the
# caller imported jax first)
from .telemetry import compile_account as _compile_account  # noqa: E402

_compile_account.install()
_compile_account.note_package_import(_time.perf_counter() - _IMPORT_START)
