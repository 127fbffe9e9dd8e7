"""Operator corpus.

Each module registers ops into the global OpInfoMap at import. The set
mirrors the reference's ~373 registered op types
(/root/reference/paddle/fluid/operators/) in waves; each op's docstring
cites the reference file it is parity with. Kernels are pure JAX —
compiled by XLA for TPU — with Pallas used for hot fused paths (see
``fused_ops``)."""
from . import elementwise_ops  # noqa: F401
from . import activation_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import matmul_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import reduce_ops  # noqa: F401
from . import conv_ops  # noqa: F401
from . import norm_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import compare_ops  # noqa: F401
from . import metrics_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import fused_ops  # noqa: F401
from . import amp_ops  # noqa: F401
from . import distributed_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import beam_search_ops  # noqa: F401
from . import nce_ops  # noqa: F401
from . import proposal_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import tail_ops  # noqa: F401
from . import tail_ops2  # noqa: F401
from . import gap_ops  # noqa: F401
from . import detection_tail_ops  # noqa: F401
from . import tree_ops  # noqa: F401
from . import var_conv_ops  # noqa: F401
from . import hybrid_parallel_ops  # noqa: F401
from . import ctr_ops  # noqa: F401
from . import tail_ops3  # noqa: F401
from . import text_match_ops  # noqa: F401
from . import eval_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import kda_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import sparse_attn_ops  # noqa: F401
from . import hyper_connection_ops  # noqa: F401
