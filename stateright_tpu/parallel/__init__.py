"""The TPU execution layer: tensor-form models and the wavefront BFS engine.

The reference has one model form and one execution strategy family (threaded
graph search over Python-like heap objects — reference ``src/checker/bfs.rs``).
This framework adds a second, device-native form: states encoded as fixed-width
``uint64`` rows, transitions expanded as a jitted batched function with static
action arity, dedup via an HBM hash table, and properties evaluated as fused
boolean kernels per wavefront (see ``SURVEY.md`` §7).

Public surface:
 - :class:`TensorModel` / :class:`BitPacker` (``tensor_model.py``)
 - :class:`TpuChecker` (``wavefront.py``) via ``model.checker().spawn_tpu()``
 - :class:`MeshTpuChecker` (``mesh.py``) via ``spawn_tpu(devices=N)``: the
   same program over a device mesh built by :func:`build_mesh`
"""

import jax

jax.config.update("jax_enable_x64", True)

from .tensor_model import BitPacker, TensorBackedModel, TensorModel  # noqa: E402,F401
from .wavefront import TpuChecker  # noqa: E402,F401
from .mesh import MeshTpuChecker  # noqa: E402,F401
from .partition import build_mesh  # noqa: E402,F401
