"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up rebuild of the capabilities of PaddlePaddle ~v0.11 (the Fluid
program-as-data core, the layer/op library, the v2 data pipeline, the
distributed pserver/master generation) designed TPU-first on JAX/XLA:

* A *Program* is still data (blocks of ops over named variables, mirroring
  the capability of ``framework.proto`` ProgramDesc — see reference
  ``paddle/framework/framework.proto:148``), but instead of a per-op C++
  interpreter (reference ``paddle/framework/executor.cc:79``) the Executor
  lowers a whole program to ONE pure function ``(state, feed) -> (state',
  fetches)`` and hands it to XLA via ``jax.jit``.  Everything fuses; there is
  no per-op dispatch at runtime.
* Autodiff is ``jax.grad`` over the traced forward prefix (the analog of
  ``append_backward`` / ``backward.cc:415 MakeBlockBackward``), surfaced
  through the same ``<param>@GRAD`` variable convention so optimizer ops,
  regularizers and clippers stay ordinary ops in the program.
* Variable-length sequences (the reference's LoD system,
  ``paddle/framework/lod_tensor.h``) are dense padded tensors + explicit
  length/segment metadata, with mask-aware sequence ops — the static-shape
  form XLA wants.
* Multi-device execution is a ``jax.sharding.Mesh`` + sharding annotations,
  replacing MultiGradientMachine ring merge, parallel_do and the NCCL ops
  with ICI collectives inserted by XLA.
"""

from . import core
from .core import compile_cache as _compile_cache

# one persistent compile cache for every executable the package builds,
# placed from outside (JAX_COMPILATION_CACHE_DIR) or at
# <checkout>/.jax_cache — core/compile_cache.py
_compile_cache.configure()

from .core import (
    Program,
    Variable,
    Executor,
    Scope,
    global_scope,
    default_main_program,
    default_startup_program,
    program_guard,
    CPUPlace,
    TPUPlace,
    unique_name,
)
from . import initializer
from .param_attr import ParamAttr
from . import learning_rate_decay
from . import layers
from . import ops
from . import nets
from . import optimizer
from . import regularizer
from . import clip
from . import backward
from .backward import append_backward
from . import io
from . import evaluator
from . import metrics
from . import reader
from . import dataset
from . import data_feeder
from .data_feeder import DataFeeder
from . import parallel
from . import observability
from . import analysis
from . import tune
from . import resilience
from . import serving
from . import profiler
from . import trainer
from . import models
from . import inference
from . import distributed
from . import flags
from .flags import FLAGS
from . import memory_optimization_transpiler
from .memory_optimization_transpiler import (
    gradient_accumulation, memory_optimize, release_memory)
from . import checkgrad
from .checkgrad import check_gradients
from . import compat
from . import image
from . import net_drawer
from . import parameters
from . import plot
from . import native

__version__ = "0.1.0"
