"""The train step's ``jax.named_scope`` names, defined once.

XLA keeps the scope path of every instruction in the compiled module's
``metadata={op_name=...}``, through ``jax.vjp`` pullbacks
(``transpose(jvp(<scope>))``) and ``jax.checkpoint`` recomputes
(``.../rematted_computation/...``), so each op of a device trace can be
put down to its layer by instruction name (``core.profiler.scope_spans``
and ``core.profiler.scope_layers``).  Scopes are metadata only: XLA
neither fuses nor schedules by them.

This module imports nothing, so the model layers and the kernels can name
their scopes without depending on the trainer side.
"""

#: Prefix of every forward scope.
FWD_PREFIX = "fwd_"
#: Forward of the DAG step (``issue='dag'``), one per ``jax.vjp`` forward
#: call: the token lookup; the tail layers after the scan (archs that have
#: them); final norm, output projection and the loss.  ``fwd_seg{j}``
#: (:func:`fwd_seg`) is the ``j``-th scan segment.
FWD_EMBED = "fwd_embed"
FWD_TAIL = "fwd_tail"
FWD_HEAD = "fwd_head"
#: Forward of the ``post`` step: its one loss over the whole model, inside
#: its ``value_and_grad``; the backward ops then carry
#: ``transpose(jvp(fwd_model))``.
FWD_MODEL = "fwd_model"
#: Prefix of every backward scope of the DAG step.
BWD_PREFIX = "bwd_"
#: Backward of the DAG step: each pullback call and the write of its
#: gradient into the step's accumulator (``bwd_seg{j}``: :func:`bwd_seg`).
#: A segment's remat recompute runs under its ``bwd_seg{j}``.  The group
#: all-reduces issued between them carry their own :func:`wfbp_group`
#: scopes (``core/sync``).
BWD_HEAD = "bwd_head"
BWD_TAIL = "bwd_tail"
BWD_EMBED = "bwd_embed"
#: The optimizer update, in every train-step body.
OPTIMIZER = "optimizer"
#: Scores, mask, softmax and weighted sum of ``models/layers.gqa_attention``
#: (not the Q/K/V/O projections): what a flash kernel replaces.  Training
#: and serving alike.
ATTENTION = "attention"
#: ``name=`` of the flash attention kernels' three ``pallas_call``s
#: (forward; backward dQ and dK/dV), under ``attention``.
FLASH_FWD = "flash_fwd"
FLASH_DQ = "flash_dq"
FLASH_DKV = "flash_dkv"
FLASH_KERNELS = (FLASH_FWD, FLASH_DQ, FLASH_DKV)
#: The held-expert MoE layer of ``models/moe`` and its four parts: routing
#: over all experts, sorting the (token, choice) rows into the held
#: experts' groups, the experts' grouped matmuls, and the gated scatter
#: back to the tokens.
MOE = "moe"
MOE_ROUTE = "moe_route"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
#: ``name=`` of the grouped-matmul kernels' ``pallas_call``s
#: (``kernels/grouped_matmul``): rows x (d -> f) per group (forward and
#: activation gradient), and per-group x^T dy (weight gradient); under
#: ``moe_experts``.
MOE_GMM = "moe_gmm"
MOE_TGMM = "moe_tgmm"
MOE_KERNELS = (MOE_GMM, MOE_TGMM)
#: ``name=`` of the row kernels' ``pallas_call``s
#: (``kernels/grouped_matmul/rows``), which move only the routed rows:
#: rows written in the layout a row DMA can address; buffer rows gathered
#: from token rows (dispatch, and the combine's gradient); token rows
#: summed from their held buffer rows (combine, and the dispatch's
#: gradient).  Under ``moe_dispatch`` and ``moe_combine``.
MOE_ROWS_PACK = "moe_rows_pack"
MOE_ROWS_GATHER = "moe_rows_gather"
MOE_ROWS_SUM = "moe_rows_sum"
MOE_ROW_KERNELS = (MOE_ROWS_PACK, MOE_ROWS_GATHER, MOE_ROWS_SUM)
#: ``name=`` of the comm_pack kernels' two ``pallas_call``s.
COMM_PACK_PACK = "comm_pack_pack"
COMM_PACK_UNPACK = "comm_pack_unpack"


def fwd_seg(j: int) -> str:
    """Forward scope of the DAG step's ``j``-th scan segment."""
    return f"{FWD_PREFIX}seg{j}"


def bwd_seg(j: int) -> str:
    """Backward scope of the DAG step's ``j``-th scan segment."""
    return f"{BWD_PREFIX}seg{j}"


def wfbp_group(gi: int, lo: int, hi: int) -> str:
    """Scope of schedule group ``gi`` (backward issue order), which covers
    layers ``lo..hi``: its pack, all-reduce and unpack."""
    return f"wfbp_group{gi}_l{lo}_{hi}"
