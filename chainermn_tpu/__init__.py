"""chainermn_tpu — TPU-native distributed training framework.

From-scratch SPMD re-design of the reference ChainerMN
(``abiraja2004/chainermn``; see ``SURVEY.md``): communicators over
:class:`jax.sharding.Mesh` instead of NCCL/MPI, collectives as XLA ops inside
jitted steps, differentiable comm functions via ``shard_map`` AD, and
training/data/fault-tolerance integration re-built on optax/orbax.

API facade (reference anchor: ``chainermn/__init__.py``).
"""

from chainermn_tpu.comm import (
    CommunicatorBase,
    DummyCommunicator,
    XlaCommunicator,
    create_communicator,
    flat_mesh,
    hybrid_mesh,
    ragged_permute,
    ragged_send,
    topology_mesh,
)
from chainermn_tpu.distributed import (
    init_distributed,
    is_initialized,
    shutdown_distributed,
)

__version__ = "0.3.0"

from chainermn_tpu import comm  # noqa: E402
from chainermn_tpu import functions  # noqa: E402
from chainermn_tpu import links  # noqa: E402
from chainermn_tpu.datasets import (  # noqa: E402
    create_empty_dataset,
    scatter_dataset,
)
from chainermn_tpu.extensions import (  # noqa: E402
    create_multi_node_checkpointer,
    create_multi_node_evaluator,
)
from chainermn_tpu import global_except_hook  # noqa: E402
from chainermn_tpu import observability  # noqa: E402
from chainermn_tpu import resilience  # noqa: E402
from chainermn_tpu.resilience import (  # noqa: E402
    HEALTH_EXIT_CODE,
    PREEMPTION_EXIT_CODE,
    FailureDetector,
    PeerFailedError,
    PreemptionGuard,
    RankDivergedError,
    RetryPolicy,
    TrainingHealthGuard,
)

global_except_hook._add_hook_if_enabled()
from chainermn_tpu.iterators import (  # noqa: E402
    create_device_prefetch_iterator,
    create_multi_node_iterator,
    create_synchronized_iterator,
)
from chainermn_tpu.optimizers import (  # noqa: E402
    MultiNodeOptimizer,
    TrainState,
    ZeroMultiNodeOptimizer,
    ZeroTrainState,
    create_multi_node_optimizer,
    create_zero_optimizer,
    zero_clip_by_global_norm,
)

__all__ = [
    "CommunicatorBase",
    "DummyCommunicator",
    "XlaCommunicator",
    "create_communicator",
    "init_distributed",
    "shutdown_distributed",
    "is_initialized",
    "flat_mesh",
    "hybrid_mesh",
    "topology_mesh",
    "ragged_permute",
    "ragged_send",
    "comm",
    "functions",
    "links",
    "create_multi_node_optimizer",
    "create_zero_optimizer",
    "ZeroMultiNodeOptimizer",
    "ZeroTrainState",
    "zero_clip_by_global_norm",
    "MultiNodeOptimizer",
    "TrainState",
    "create_multi_node_evaluator",
    "create_multi_node_checkpointer",
    "scatter_dataset",
    "create_empty_dataset",
    "create_multi_node_iterator",
    "create_synchronized_iterator",
    "create_device_prefetch_iterator",
    "observability",
    "resilience",
    "FailureDetector",
    "PeerFailedError",
    "PreemptionGuard",
    "RankDivergedError",
    "TrainingHealthGuard",
    "RetryPolicy",
    "PREEMPTION_EXIT_CODE",
    "HEALTH_EXIT_CODE",
]
