"""Operations and bytes from shapes: the benchmark's own arithmetic.

Nothing here asks the compiler (``cost_analysis`` counts recomputation
and fusion artefacts): every figure follows from the sizes in a
configuration file and a traffic file, so no change to the program can
move it.
"""

from __future__ import annotations


# -- collectives: nccl-tests bus bytes (doc/PERFORMANCE.md) -----------------

#: bus bytes of one call as a multiple of S, the full per-rank buffer
#: (the larger of what a rank sends and what it receives), at world P.
#: allreduce moves each byte out and back, 2(P-1)/P; the other three move
#: (P-1)/P of the buffer once.
_BUS_FACTOR = {
    "allreduce": lambda p: 2.0 * (p - 1) / p,
    "allgather": lambda p: (p - 1) / p,
    "reduce_scatter": lambda p: (p - 1) / p,
    "alltoall": lambda p: (p - 1) / p,
}


def bus_bytes(op: str, nbytes: int, world: int) -> float:
    """nccl-tests bus bytes of one ``op`` over a ``nbytes`` per-rank
    buffer on ``world`` ranks."""
    try:
        factor = _BUS_FACTOR[op]
    except KeyError:
        raise KeyError(f"no bus-byte rule for collective {op!r}") from None
    return factor(world) * nbytes


# -- dense decoder (GPT-BigCode block): train FLOPs a token -----------------


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication of the forward
    pass: q, k, v, o, the two FFN matrices a layer, and the (tied) LM
    head.  The embedding lookup, the position table and the norms are
    not matmuls."""
    d = cfg["n_embd"]
    head = d // cfg["n_head"]
    kv = head * (1 if cfg["multi_query"] else cfg["n_head"])
    layer = d * d + 2 * d * kv + d * d + 2 * d * cfg["n_inner"]
    return cfg["n_layer"] * layer + d * cfg["vocab_size"]


def attention_train_flops(cfg: dict, seq: int) -> float:
    """Causal attention, forward and backward, of ONE sequence through
    ONE layer: QK^T and PV forward (2 * 2*T*T*d, halved by the mask) and
    four such products backward.  The flash kernel's backward recomputes
    QK^T; recomputation is not counted."""
    d = cfg["n_embd"]
    forward = 2 * (2.0 * seq * seq * d) / 2
    return 3 * forward


def attention_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    d = cfg["n_embd"]
    head = d // cfg["n_head"]
    kv = head * (1 if cfg["multi_query"] else cfg["n_head"])
    q = seq * d * itemsize
    k = seq * kv * itemsize
    return (2 * q + 2 * k) + (4 * q + 2 * k) + (q + 2 * k)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6 x matmul parameters (forward
    2, backward 4) plus causal attention; no recomputation, no optimizer
    (SGD is under 0.01%)."""
    attn = cfg["n_layer"] * attention_train_flops(cfg, seq) / seq
    return 6.0 * matmul_params(cfg) + attn


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take and which bound sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
