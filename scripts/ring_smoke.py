"""CI command-ring smoke: exercise the ring's HOST half — the slot
codec over the full opcode space and the fused-slot units, with numpy
only (no jax, the same footprint as the acclint gate job it runs next
to, .github/workflows/analysis.yml).
The window program is covered by the jax test tier
(tests/test_cmdring.py); this job proves the codec the device-side
decode rides stays importable and correct standalone.

Usage::

    python scripts/ring_smoke.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from accl_tpu.cmdring import (
    FUSED_BASE_OPS,
    decode_fparam,
    decode_slot,
    encode_fparam,
    encode_slot,
    encode_window,
    fused_slot_eligible,
    ring_widths,
)
from accl_tpu.constants import (
    CMDRING_FUSED_OPCODES,
    CMDRING_OPCODES,
    CMDRING_SLOT_WORDS,
    CmdOpcode,
    FusedCompute,
    Operation,
    ReduceFunction,
)


def codec_smoke() -> None:
    """Every executable opcode round-trips through the slot codec with
    its full field set."""
    for op, opcode in CMDRING_OPCODES.items():
        words = encode_slot(
            11, opcode, 256, dtype=2, function=ReduceFunction.MAX,
            root=1, nseg=2, peer=3, wire=1,
        )
        assert words.shape == (CMDRING_SLOT_WORDS,)
        d = decode_slot(words)
        assert d["opcode"] is opcode, op
        assert d["count"] == 256 and d["peer"] == 3 and d["wire"] == 1
    w = encode_window([encode_slot(0, CmdOpcode.BARRIER, 1)], 4)
    assert w.shape == (4, CMDRING_SLOT_WORDS)
    assert decode_slot(w[3])["opcode"] is CmdOpcode.NOP
    # width table sanity (the sequencer analog of IN_W/OUT_W)
    assert ring_widths(Operation.ALLREDUCE, 8, 4) == (8, 8)
    assert ring_widths(Operation.REDUCE_SCATTER, 8, 4) == (32, 8)
    assert ring_widths(Operation.ALLGATHER, 8, 4) == (8, 32)
    assert ring_widths(Operation.ALLTOALL, 8, 4) == (32, 32)
    assert ring_widths(Operation.BARRIER, 0, 4) == (1, 1)
    print("codec: ok")


def fused_smoke() -> None:
    """Fused compute slots, host half: codec round-trip with the
    Q16.16 fparam word, the fused width relations, and the planner's
    eligibility predicate — the same units the engine planner and the
    window program read, importable without jax."""
    # every fused hint maps to a slot opcode and round-trips the codec
    # with its epilogue scalar
    for fuse, opcode in CMDRING_FUSED_OPCODES.items():
        words = encode_slot(
            3, opcode, 64, dtype=2, peer=1, fparam=encode_fparam(0.5)
        )
        d = decode_slot(words)
        assert d["opcode"] is opcode, fuse
        assert decode_fparam(d["fparam"]) == 0.5  # exact: power of two
    # Q16.16: exact on power-of-two training scalars, clamped at int32
    for exact in (1.0, -1.0, 0.125, 2.0, 0.0):
        assert decode_fparam(encode_fparam(exact)) == exact
    assert abs(decode_fparam(encode_fparam(0.3)) - 0.3) < 1e-4
    assert encode_fparam(1e12) == 2 ** 31 - 1
    assert encode_fparam(-1e12) == -(2 ** 31)
    # the width RELATIONS that classify fused slots on device:
    # APPLY in == out*(size+1); ATTN_HOP in == 2*out; MATMUL_RS keeps
    # the plain reduce-scatter geometry
    assert ring_widths(
        Operation.REDUCE_SCATTER, 8, 4, fuse=FusedCompute.MATMUL_RS
    ) == (32, 8)
    assert ring_widths(
        Operation.ALLREDUCE, 8, 4, fuse=FusedCompute.APPLY
    ) == (40, 8)
    assert ring_widths(
        Operation.ALLREDUCE, 8, 4, fuse=FusedCompute.ATTN_HOP
    ) == (16, 8)
    # planner eligibility: every fuse is eligible on its base op at the
    # fused operand width, and each refusal reason fires exactly where
    # the engine counts it
    for fuse, base in FUSED_BASE_OPS.items():
        in_w, _out_w = ring_widths(base, 8, 4, fuse=fuse)
        assert fused_slot_eligible(
            fuse, base, 4, 8, in_w, np.float32
        ) is None, fuse
    cases = (
        ((99, Operation.ALLREDUCE, 4, 8, 40, np.float32),
         "unknown_fuse"),
        ((FusedCompute.APPLY, Operation.REDUCE_SCATTER, 4, 8, 40,
          np.float32), "fused_base_op"),
        ((FusedCompute.MATMUL_RS, Operation.REDUCE_SCATTER, 1, 8, 8,
          np.float32), "fused_world_too_small"),
        ((FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 40, np.int32),
         "fused_dtype"),
        ((FusedCompute.ATTN_HOP, Operation.ALLREDUCE, 4, 8, 8,
          np.float32), "fused_operand_width"),
    )
    for args, want in cases:
        assert fused_slot_eligible(*args) == want, (args, want)
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 40, np.float32,
        compressed=True,
    ) == "fused_compressed"
    print("fused: ok")


def main() -> int:
    codec_smoke()
    fused_smoke()
    print("ring smoke: all ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
