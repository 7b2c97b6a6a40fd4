"""The main path's kernels at the benchmark's real widths, COMPILED for a
v5e that is described and not attached (the TPU's compiler is installed
where these tests run): what interpret mode cannot show, a tile Mosaic
refuses or more VMEM than a kernel may use, found on the CPU at no chip
time.  Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import (only one
process may hold the TPU's library; the fixture skips where it cannot be
described), and every compile of this tier lives in this one file."""

import dataclasses
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e)


def _compile(fn, shapes, sharding):
    args = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding) for s in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _loss(attend):
    return lambda *a: jax.grad(
        lambda *b: attend(*b).astype(jnp.float32).sum(),
        argnums=tuple(range(len(a))),
    )(*a)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_kernels_compile_at_deepseek_v2_widths(one_chip, window):
    """``train_dsv2_t4096_b1``'s core: 128 heads of 128 + 64 columns, ONE
    rope key head, v heads of 128, T = 4096."""
    from accl_tpu.ops.pallas.attention import flash_attention

    B, H, T = 1, 128, 4096
    text = _compile(
        _loss(lambda q, k, v, qr, kr: flash_attention(
            q, k, v, window=window, scale=0.11472, q_rope=qr, k_rope=kr,
            interpret=False,
        )),
        [(B, H, T, 128), (B, H, T, 128), (B, H, T, 128), (B, H, T, 64),
         (B, 1, T, 64)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("case", ["t8192_mqa", "trinity_window"])
def test_flash_kernels_compile_at_the_other_cells_widths(one_chip, case):
    from accl_tpu.ops.pallas.attention import flash_attention

    H, Hkv, T, window = {
        "t8192_mqa": (32, 1, 8192, None),
        "trinity_window": (32, 4, 8192, 2048),
    }[case]
    text = _compile(
        _loss(lambda q, k, v: flash_attention(
            q, k, v, window=window, interpret=False
        )),
        [(1, H, T, 128), (1, Hkv, T, 128), (1, Hkv, T, 128)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text


def test_flash_kernels_compile_at_ling3_widths(one_chip):
    """``train_ling3_t8192_b2``'s one latent layer: 32 heads of 128 + 64
    columns, ONE rope key head, v heads of 128, two sequences of T = 8192
    (twice the DeepSeek-V2 cell's length: the backward holds 37.5 MiB)."""
    from accl_tpu.ops.pallas.attention import flash_attention

    B, H, T = 2, 32, 8192
    text = _compile(
        _loss(lambda q, k, v, qr, kr: flash_attention(
            q, k, v, scale=192 ** -0.5, q_rope=qr, k_rope=kr, interpret=False,
        )),
        [(B, H, T, 128), (B, H, T, 128), (B, H, T, 128), (B, H, T, 64),
         (B, 1, T, 64)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("block", [4, 1024])
def test_flash_kernels_compile_under_the_block_diffusion_layout(one_chip, block):
    """``train_sdar_t4096_b2``'s core: 32 query heads of 128 on 4 KV heads,
    ``[noisy ; clean]`` of L = 4,096 (T = 8,192, the last length the
    kernels take), blocks of 4 (masked tiles in line, a noisy q tile's two
    ranges) and of 1,024 (two tiles a block, nothing masked); the
    backward's VMEM sum is the causal T = 8,192's, 25 MiB."""
    from accl_tpu.ops.pallas import attention as fa

    L = 4096
    text = _compile(
        _loss(lambda q, k, v: fa.flash_attention(
            q, k, v, block_diffusion=(L, block), interpret=False
        )),
        [(1, 32, 2 * L, 128), (1, 4, 2 * L, 128), (1, 4, 2 * L, 128)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text
    assert fa._flash_bwd_vmem_bytes(2 * L, 128, 512, 2) == 25 * 2**20
    assert fa.flash_tile_pairs(2 * L, block_diffusion=(L, block)) == 80


def test_grouped_matmuls_compile_at_deepseek_v2_widths(one_chip):
    """20 held experts of 5120 x 1536 over the cell's 6,144 buffer rows:
    tiles that divide 5120 and 1536 (``grouped_matmul.tiles``)."""
    from accl_tpu.ops.pallas.grouped_matmul import grouped_matmul, tiles

    assert tiles("gmm_fwd", 6144, 5120, 1536, jnp.bfloat16) == (256, 1280, 1536)
    assert tiles("gmm_fwd", 6144, 1536, 5120, jnp.bfloat16) == (256, 1536, 1280)
    sizes = jax.ShapeDtypeStruct((20,), jnp.int32, sharding=one_chip)

    def f(rows, w1, w2, sizes):
        def loss(rows, w1, w2):
            h = grouped_matmul(rows, w1, sizes, interpret=False)
            return grouped_matmul(h, w2, sizes, interpret=False).astype(
                jnp.float32
            ).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(rows, w1, w2)

    args = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in [(6144, 5120), (20, 5120, 1536), (20, 1536, 5120)]
    ]
    text = jax.jit(f).lower(*args, sizes).compile().as_text()
    for name in ("gmm_fwd", "gmm_dlhs", "gmm_drhs"):
        assert name in text


#: the held cells' placements: (buffer rows, tokens, k, D, held experts)
PLACEMENTS = {
    "trinity_sdar_weighted": (32768, 16384, 8, 2048, 16, True),
    "trinity_sdar_cotangent": (32768, 16384, 8, 2048, 16, False),
    "deepseek_v2_weighted": (6144, 4096, 6, 5120, 20, True),
}


@pytest.mark.parametrize("case", PLACEMENTS)
def test_placement_kernel_compiles_at_the_held_cells_shapes(one_chip, case):
    """``place_rows`` with its weights (the combine) and without (the
    dispatch gather's cotangent), bf16 rows; what a grid step keeps in
    VMEM, reckoned from the shapes, under the limit the call passes."""
    from accl_tpu.ops.pallas import place_rows as pr

    R, N, k, D, E, weighted = PLACEMENTS[case]
    tt, slab = pr.tiles(N, D)
    assert (tt, slab) == (256, 512)
    assert pr.vmem_bytes(tt, slab, k, D, 2) <= (20 if D == 2048 else 40) << 20
    shape = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    args = [
        shape((R, D), jnp.bfloat16), shape((N, k), jnp.int32),
        shape((E * (N // tt) + 1,), jnp.int32),
    ] + [shape((N, k), jnp.float32)] * weighted
    text = jax.jit(
        lambda *a: pr.place_rows(*a, interpret=False)
    ).lower(*args).compile().as_text()
    assert "place_rows" in text


def test_kda_kernels_compile_at_ling3_shapes(one_chip):
    """``train_ling3_t8192_b2``'s KDA core alone, forward and gradient: 2 x
    32 head-sequences of 8,192 tokens, ``dk = dv = 128``, float32: Mosaic
    takes every tile and the VMEM each kernel asks for."""
    from accl_tpu.ops.pallas import kda

    B, H, T, d = 2, 32, 8192, 128
    rows = jax.ShapeDtypeStruct((B, H, T, d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one_chip)
    core = lambda *a: kda.kda(*a, interpret=False)
    text = jax.jit(core).lower(rows, rows, rows, rows, beta).compile().as_text()
    assert "kda_fwd" in text and "kda_bwd" not in text
    text = jax.jit(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2, 3, 4)
    )).lower(rows, rows, rows, rows, beta).compile().as_text()
    assert "kda_fwd" in text and "kda_bwd" in text


def test_ssd_kernels_compile_at_nemotron3_shapes(one_chip):
    """``train_nemotron3_t8192_b1``'s Mamba-2 core alone, forward and
    gradient: 8,192 tokens, 128 heads of 64 in 8 groups, state 128,
    token-major float32 rows: Mosaic takes every tile (a group's 1,024
    columns a block, the heads' scalars as columns of 16 lanes), the lane
    broadcasts of a head's column and the VMEM each kernel asks for."""
    from accl_tpu.ops.pallas import ssd

    B, T, H, width, G, N = 1, 8192, 128, 64, 8, 128
    struct = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    args = (
        struct(B, T, H * width), struct(B, T, G * N), struct(B, T, G * N),
        struct(B, H, T), struct(B, H, T), struct(H),
    )
    assert ssd.takes(args[0].shape, args[1].shape, H, G)
    core = lambda *a: ssd.ssd(*a, G, interpret=False)
    text = jax.jit(core).lower(*args).compile().as_text()
    assert "ssd_fwd" in text and "ssd_bwd" not in text
    text = jax.jit(jax.grad(
        lambda *a: core(*a).sum(), argnums=tuple(range(6))
    )).lower(*args).compile().as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text


#: a chain of ``train_ling3_t8192_b2``'s KDA mixer: (the kernels' names,
#: the call, its operands' shapes and types)
def _mixer_chains():
    from accl_tpu.ops.pallas import kda_mixer as km

    B, H, T, d = 2, 32, 8192, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    flat, taps = ((B, T, H * d), bf16), ((4, H * d), bf16)
    conv = lambda **how: lambda x, t: km.conv_in(x, t, H, interpret=False, **how)
    return {
        "q": ("kda_in", conv(unit=True, scale=d ** -0.5), [flat, taps]),
        "v": ("kda_in", conv(unit=False), [flat, taps]),
        "decay": (
            "kda_decay", lambda *a: km.decay_in(*a, -5.0, interpret=False),
            [flat, ((H * d,), f32), ((H,), f32)],
        ),
        "out": (
            "kda_out", lambda *a: km.gated_out(*a, 1e-6, bf16, interpret=False),
            [((B, H, T, d), f32), flat, ((d,), bf16)],
        ),
    }


@pytest.mark.parametrize("chain", ["q", "v", "decay", "out"])
def test_kda_mixer_kernels_compile_at_ling3_shapes(one_chip, chain):
    """``train_ling3_t8192_b2``'s three float32 chains round the KDA core,
    each alone, forward and gradient: bfloat16 projections of 2 x 8,192
    rows and 32 heads of 128 columns (q with the norm and the scale, v
    without, the decay, the output norm and gate): Mosaic takes every
    tile, the unaligned row windows of the convolution and the VMEM each
    kernel asks for."""
    _chain_compiles(*_mixer_chains()[chain], one_chip)


def _chain_compiles(name, call, operands, one_chip):
    """A mixer's chain alone: its forward kernel in the forward's text, its
    backward kernel in the gradient's."""
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in operands]
    text = jax.jit(call).lower(*args).compile().as_text()
    assert name + "_fwd" in text and name + "_bwd" not in text
    text = jax.jit(jax.grad(
        lambda *a: call(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args))),
    )).lower(*args).compile().as_text()
    assert name + "_bwd" in text


#: a chain of ``train_nemotron3_t8192_b1``'s Mamba-2 mixer: (the kernels'
#: names, the call, its operands' shapes and types)
def _mamba_chains():
    from accl_tpu.ops.pallas import mamba_mixer as mm

    B, T, wide, state, G = 1, 8192, 8192, 1024, 8
    bf16, f32 = jnp.bfloat16, jnp.float32
    conv = lambda *a: mm.conv_silu(*a, interpret=False)
    operands = lambda C: [((B, T, C), bf16), ((4, C), bf16), ((C,), bf16)]
    return {
        "x": ("mamba_in", conv, operands(wide)),
        "b_or_c": ("mamba_in", conv, operands(state)),
        "out": (
            "mamba_out",
            lambda *a: mm.gated_group_norm(*a, G, 1e-5, bf16, interpret=False),
            [((B, T, wide), f32), ((B, T, wide), bf16), ((wide,), bf16)],
        ),
    }


@pytest.mark.parametrize("chain", ["x", "b_or_c", "out"])
def test_mamba_mixer_kernels_compile_at_nemotron3_shapes(one_chip, chain):
    """``train_nemotron3_t8192_b1``'s two float32 chains round the SSD core,
    each alone, forward and gradient: the convolution, bias and SiLU of a
    bfloat16 projection of 8,192 rows at x's 8,192 columns and at B's and
    C's 1,024, 4 taps; the gate and the norm over 8 groups of 1,024 columns
    of the core's float32 ``y``: Mosaic takes every tile, the unaligned row
    windows of the convolution, a group's sweeps over its eight lane tiles
    and the VMEM each kernel asks for."""
    from accl_tpu.ops.pallas import mamba_mixer as mm

    assert mm.takes(8192, 8, 4) and mm.takes(1024, taps=4)
    _chain_compiles(*_mamba_chains()[chain], one_chip)


def _step_text(cell_name, n_layers, device, monkeypatch, layers=None):
    return _step(cell_name, n_layers, device, monkeypatch, layers).as_text()


def _step(cell_name, n_layers, device, monkeypatch, layers=None, lr=None):
    """A train cell's step (``make_sharded_train_step`` on a world of the
    one described chip, the cell's widths, batch and length; its depth cut
    to its first ``n_layers``, which the table's gradient does not see, or
    to the ``layers`` of its pattern named by index), compiled; the Pallas
    kernels compiled too, as the chip has them."""
    from accl_tpu.models import init_params, make_sharded_train_step
    from accl_tpu.models.transformer import normalize_spec, param_specs
    from perfbench import manifest

    for module in (
        "attention", "grouped_matmul", "place_rows", "kda", "kda_mixer", "ssd",
        "mamba_mixer",
    ):
        monkeypatch.setattr(
            importlib.import_module("accl_tpu.ops.pallas." + module),
            "default_interpret", lambda interpret=None: bool(interpret),
        )
    cell = manifest.cell(manifest.load(), cell_name)
    driver = importlib.import_module(
        "perfbench.drivers." + cell["traffic"]["driver"]
    )
    cfg = driver.program_config(cell["config"])
    if layers is not None:
        kept = tuple(cfg.layers[i] for i in layers)
    else:
        kept = cfg.layers[:n_layers] if cfg.layers else cfg.layers
    cfg = dataclasses.replace(
        cfg, attention="flash", n_layers=n_layers, layers=kept
    )
    mesh = Mesh(np.array([device]).reshape(1, 1), ("dp", "tp"))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, normalize_spec(spec)),
        ),
        shapes, param_specs(cfg),
    )
    tok = jax.ShapeDtypeStruct(
        (int(cell["traffic"]["batch"]), int(cell["traffic"]["seq"])),
        jnp.int32, sharding=NamedSharding(mesh, P()),
    )
    if lr is None:
        lr = float(cell["traffic"]["lr"])
    step, _ = make_sharded_train_step(cfg, mesh, lr=lr)
    return step.lower(params, tok, tok).compile()


def _entry_instructions(text):
    """``{name: the rest of its line}`` of the ENTRY computation's
    instructions."""
    start = text.find("\nENTRY ")
    return dict(re.findall(
        r"^\s*(?:ROOT )?%(\S+) = (.*)$", text[start: text.find("\n}", start)], re.M
    ))


def _products(text, names, shape):
    """Those of the ENTRY instructions ``names`` that are a fusion holding a
    matmul (a ``convolution``, as the chip has it) and writing a ``shape``:
    under a mixer's scope and at a projection's shape, the projection's
    forward products (and whatever cotangent has that shape)."""
    made = _entry_instructions(text)
    found = []
    for name in names:
        fusion = re.match(r"(\(.*?\)|\S+) fusion\(.*?calls=(%[\w.\-]+)", made[name])
        if fusion is None or shape not in fusion[1]:
            continue
        body = text[text.find(f"\n{fusion[2]} ("):]
        if " convolution(" in body[: body.find("\n}")]:
            found.append(name)
    return found


def _flash_once(scope):
    """A rematerialised block's attention core, the ENTRY instructions
    ``scope`` of one layer's: ``flash_fwd`` ONCE beside its ``flash_bwd``.
    Since PR 55 the block keeps the kernel's ``o`` and ``lse`` by name
    (``_flash_vjp_fwd``; a softmax mixer also q, k, v as the core takes
    them), so the backward's replay of the block does not call the forward
    kernel again; the parent's text has it twice a layer."""
    for kernel in ("flash_fwd", "flash_bwd"):
        assert sum(n.startswith(kernel) for n in scope) == 1, (kernel, scope)


def _table_ops(text, V, D):
    """``{instruction: its text}`` of the ENTRY instructions that write a
    ``bf16[V, D]``."""
    start = text.find("\nENTRY ")
    entry = text[start: text.find("\n}", start)]
    found = re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = bf16\[{V},{D}\]\S* (.*)$", entry, re.M
    )
    return dict(found)


def test_deepseek_v2_step_places_the_table_gradient_by_a_matmul(
    v5e, monkeypatch
):
    """4,096 cotangent rows of 5,120 columns on 12,800 table rows: no
    scatter into ``bf16[12800,5120]``, and one op under
    ``accl.embed::grad`` that holds the matmul (a leading dense layer and
    one expert layer of the cell's five)."""
    from perfbench import scope_ops

    text = _step_text("train_dsv2_t4096_b1", 2, v5e, monkeypatch)
    ops = _table_ops(text, 12800, 5120)
    assert ops and not any("scatter" in op for op in ops.values())
    assert not re.search(r"bf16\[12800,5120\]\S* scatter\(", text)
    under = scope_ops.scopes_of(text).get("accl.embed::grad")
    assert under and set(under) <= set(ops)
    # the computation that op calls holds the matmul
    called = re.search(r"calls=(%\S+?)[,)]", ops[under[0]])[1]
    body = text[text.find(f"\n{called} ("):]
    assert re.search(
        r"f32\[12800,5120\]\S* convolution\(", body[: body.find("\n}")]
    )


def test_starcoder_step_keeps_its_scatter_add(v5e, monkeypatch):
    """8,192 rows of 4,096 columns on 49,152 table rows: XLA's scatter-add
    stays, under the same scope."""
    from perfbench import scope_ops

    text = _step_text("train_t8192_b1", 1, v5e, monkeypatch)
    ops = _table_ops(text, 49152, 4096)
    under = scope_ops.scopes_of(text).get("accl.embed::grad")
    assert under
    scatters = [n for n in under if n in ops and "scatter-add" in ops[n]]
    assert len(scatters) == 1
    assert re.search(r"bf16\[49152,4096\]\S* scatter\(", text)


def test_trinity_step_places_the_held_rows_by_the_kernel(v5e, monkeypatch):
    """16,384 buffer rows of 2,048 columns on 16,384 tokens, twice a MoE
    layer: one ``place_rows`` kernel under ``accl.moe::combine`` and one
    under ``accl.moe::dispatch`` (the cell's first two layers hold one MoE
    layer), and no scatter as wide as the model but the embedding
    table's."""
    from perfbench import scope_ops

    text = _step_text("train_trinity_t8192_b2", 2, v5e, monkeypatch)
    start = text.find("\nENTRY ")
    entry = text[start: text.find("\n}", start)]
    assert not re.search(r"= f32\[\d+,2048\]\S* scatter\(", text)
    scopes = scope_ops.scopes_of(text)
    placed = re.findall(r"%(place_rows\S*) = bf16\[16384,2048\]", entry)
    assert len(placed) == 2
    for scope in ("accl.moe::combine", "accl.moe::dispatch"):
        assert len(set(placed) & set(scopes[scope])) == 1


def test_ling3_step_scans_the_chunks_and_keeps_no_square_of_the_length(
    v5e, monkeypatch
):
    """One KDA expert layer and the latent expert layer of the cell's
    seven, 2 x 8,192 tokens, under ``remat`` as the cell runs: the KDA
    core is the kernels ``kda_fwd`` / ``kda_bwd`` under ``accl.attn::kda``
    as ``flash_fwd`` / ``flash_bwd`` are under ``accl.attn::mla`` (a KDA
    layer: the forward, the replayed forward and the backward), the scan
    over the chunks fused into them (no ``while`` under the scope, nothing
    of it in a loop's body, which ``scopes_of`` does not walk and the
    driver's ``scoped_instructions`` does), no float32 array of the
    inputs' size there but the kernels' operands and results; the mixer's
    float32 chains round the core are the kernels of ``kda_mixer`` under
    ``accl.attn::kda_proj`` (q, k and v in, the decay in, out: each
    forward kernel twice, each backward once: the chains still replay),
    but the five bf16 projections they read are multiplied out ONCE, kept
    under ``remat`` by name (PR 49; the parent's text has each twice), and
    XLA makes no float32
    array of a projection's size there, let alone a backward
    convolution's stack of four (``f32[4,2,8192,4096]``); the step's
    scratch no more than the parent's and the kept projections, the latent
    core the flash kernels,
    the held rows placed by the kernel, and no array a square of the
    length (memory linear in T)."""
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3

    compiled = _step("train_ling3_t8192_b2", 2, v5e, monkeypatch, layers=(5, 6))
    text = compiled.as_text()
    entry = scope_ops.scopes_of(text)
    every = train_steps_ling3.scoped_instructions(text)
    core, chains = entry["accl.attn::kda"], entry["accl.attn::kda_proj"]
    kda_layers = 1
    assert sum(n.startswith("kda_fwd") for n in core) == 2 * kda_layers
    assert sum(n.startswith("kda_bwd") for n in core) == kda_layers
    assert not any(n.startswith("while") for n in core)
    assert set(core) == set(every["accl.attn::kda"])
    assert set(chains) == set(every["accl.attn::kda_proj"])
    kernels = {
        "kda_in_fwd": 6, "kda_in_bwd": 3, "kda_decay_fwd": 2,
        "kda_decay_bwd": 1, "kda_out_fwd": 2, "kda_out_bwd": 1,
    }
    for kernel, count in kernels.items():
        assert sum(n.startswith(kernel) for n in chains) == count * kda_layers, kernel
    # q, k, v and the two gates from the hidden state once each (the parent
    # of PR 49 replayed them: 11), and ``wo``'s cotangent, the same shape
    assert len(_products(text, chains, "bf16[2,8192,4096]")) == (5 + 1) * kda_layers
    # whatever else under either scope is as large as q is a view of a
    # kernel's operand or result, no array of its own
    made = _entry_instructions(text)
    for name in core + chains:
        shape, op = re.match(r"(\(.*?\)|\S+) ([\w-]+)\(", made[name]).groups()
        assert "f32[4,2,8192,4096]" not in shape, name
        if re.search(r"f32\[(2,32|64),8192,128\]|f32\[2,8192,4096\]", shape):
            assert op in ("custom-call", "bitcast", "get-tuple-element"), name
    # the parent's kernels for the core alone, the same cut: 6,177,251,840
    # bytes of scratch (10,363,852,800 under the XLA form of the core;
    # 4,722,778,112 when the chains' kernels came)
    assert compiled.memory_analysis().temp_size_in_bytes <= 6_177_251_840
    # and no more than PR 49's parent (4,722,778,112) with the five kept
    # projections of the one KDA layer, 5 x 2 x 8,192 x 4,096 x 2 bytes (this
    # cut reads 4,722,423,296: its peak is elsewhere; the whole cell's six
    # layers add 3.35 GB to 5.37)
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        4_722_778_112 + 671_088_640 * kda_layers
    )
    assert entry["accl.attn::latent"]
    _flash_once(entry["accl.attn::mla"])
    assert re.search(r"%place_rows\S* = bf16\[16384,2560\]", text)
    # one head's scores over the whole length would be 2^32 elements; the
    # largest array here is the float32 logits' 16,384 x 19,648 (2^28.3)
    largest = max(
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    )
    assert largest == 16384 * 19648


def test_nemotron3_step_scans_the_chunks_and_places_latent_rows(
    v5e, monkeypatch
):
    """One Mamba-2 block, one LatentMoE block and the attention block of the
    cell's eleven, 1 x 8,192 tokens, under ``remat`` as the cell runs: the
    SSD core is the kernels ``ssd_fwd`` / ``ssd_bwd`` under
    ``accl.attn::ssd`` (a Mamba-2 block: the forward, the replayed forward
    and the backward), the scan over the 64 chunks fused into them (no
    ``while`` under the scope and nothing of it in a loop's body, which
    ``scopes_of`` does not walk and the driver's ``scoped_instructions``
    does), no decay square (8 x 16 x 64 x 128 x 128 = 2^27 elements a block
    under the XLA form) anywhere in the text; the mixer round it under
    ``accl.attn::mamba_proj``, its two float32 chains the kernels of
    ``mamba_mixer`` there (x, B and C in, the gated norm out: each forward
    kernel twice, each backward once: the chains still replay), the five bf16
    projections they and ``dt`` read multiplied out ONCE, kept under ``remat``
    by name (PR 49; the parent's text has each twice), XLA making no float32 array of x's
    size under the scope, no copy of y into the norm's ``(B, T, G, C / G)``
    view (``f32[1024,8,8,1024]`` by token tile) and no such view at all;
    ``W_down`` and ``W_up`` under ``accl.moe::latent``; the held rows are
    1,024 wide and placed by the kernel (``_gathers_win`` at 45,056 rows, 180,224 entries: the gathers
    would take 1.2 ms); attention is the flash kernels on 2 KV heads; no
    array is a square of the length, and the largest are the held experts'
    float32 weight gradients (64 x 1,024 x 2,688: more than the float32
    logits' 8,192 x 16,384, which come next); the step's scratch no more
    than the parent's."""
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3

    compiled = _step(
        "train_nemotron3_t8192_b1", 3, v5e, monkeypatch, layers=(0, 1, 9)
    )
    text = compiled.as_text()
    entry = scope_ops.scopes_of(text)
    every = train_steps_ling3.scoped_instructions(text)
    for scope in ("accl.attn::ssd", "accl.attn::mamba_proj",
                  "accl.moe::latent", "accl.moe::route", "accl.moe::dispatch",
                  "accl.moe::experts", "accl.moe::combine",
                  "accl.moe::shared", "accl.attn::core"):
        assert entry.get(scope), scope
    core, mamba_blocks = entry["accl.attn::ssd"], 1
    assert sum(n.startswith("ssd_fwd") for n in core) == 2 * mamba_blocks
    assert sum(n.startswith("ssd_bwd") for n in core) == mamba_blocks
    assert not any(n.startswith("while") for n in core)
    assert set(core) == set(every["accl.attn::ssd"])     # no loop's body
    chains = entry["accl.attn::mamba_proj"]
    assert set(chains) == set(every["accl.attn::mamba_proj"])
    kernels = {
        "mamba_in_fwd": 6, "mamba_in_bwd": 3, "mamba_out_fwd": 2, "mamba_out_bwd": 1,
    }
    for kernel, count in kernels.items():
        assert sum(n.startswith(kernel) for n in chains) == count * mamba_blocks, kernel
    # z and x from the hidden state once each and ``wo``'s cotangent, the
    # same shape (the parent of PR 49: 5); B and C once each (4); ``dt``'s
    # product, fused with its softplus into the heads' rows, once (2)
    assert len(_products(text, chains, "bf16[1,8192,8192]")) == (2 + 1) * mamba_blocks
    assert len(_products(text, chains, "bf16[1,8192,1024]")) == 2 * mamba_blocks
    assert len(_products(text, chains, "f32[1,128,8192]")) == mamba_blocks
    # whatever else under the scope is as large as x in float32 is a view of
    # a kernel's operand or result, no array of its own
    made = _entry_instructions(text)
    for name in chains:
        shape, op = re.match(r"(\(.*?\)|\S+) ([\w-]+)\(", made[name]).groups()
        if "f32[1,8192,8192]" in shape:
            assert op in ("custom-call", "bitcast", "get-tuple-element"), name
    assert not re.search(r"f32\[1024,8,8,1024\]|f32\[1,8192,8,1024\]", text)
    _flash_once(entry["accl.attn::core"])
    for kernel in ("gmm_fwd", "gmm_dlhs", "gmm_drhs"):
        assert any(kernel in n for n in entry["accl.moe::experts"]), kernel
    # forward, the replayed forward and the dispatch gather's cotangent
    placed = re.findall(r"%(place_rows\S*) = bf16\[8192,1024\]", text)
    assert len(placed) == 3
    assert re.search(r"bf16\[45056,1024\]", text)
    assert not re.search(r"\[45056,4096\]|\[8192,8192,\d+\]|\[8192,8192\]\S* dot", text)
    sizes = sorted({
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    })
    # one head's scores over the whole length would be 2^26 elements a head
    # and 2^31 for the 32: nothing here is, and nothing is the XLA form's
    # decay squares (2^27 a block) or chunk products (2^26, five dimensions)
    assert sizes[-1] == 64 * 1024 * 2688 and sizes[-2] == 8192 * 16384
    assert not re.search(r"f32\[1,8,16,64,128,(?:64|128)\]", text)
    # the parent's compile for the described chip (the chains XLA's fusions),
    # the same cut: 2,976,605,696 bytes (4,698,582,016 under the XLA form of
    # the core; 2,068,348,928 when the chains' kernels came)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2_976_605_696
    # and no more than PR 49's parent (2,068,348,928) with the five kept
    # projections of the one Mamba-2 block, 8,192 x 18,560 x 2 bytes (this
    # cut reads 2,071,933,952; the whole cell's five blocks add 1.43 GB)
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        2_068_348_928 + 304_087_040 * mamba_blocks
    )

    # the step whose update the cell's check reads the gradient from (the
    # driver's UPDATE_PROBE_RATE) is this step with ONE number changed: the
    # rate, a constant of the leaf's type at each leaf's update
    from perfbench.drivers import train_steps_nemotron3

    probe = _step(
        "train_nemotron3_t8192_b1", 3, v5e, monkeypatch, layers=(0, 1, 9),
        lr=train_steps_nemotron3.UPDATE_PROBE_RATE,
    ).as_text()
    rate = re.compile(
        r"%constant\.\d+|constant\((?:0\.0009995|0\.001|4096)\)|, metadata=\{[^}]*\}"
        # a kernel's body carries the line numbers of the call that built it
        r'|"body":"[^"]*"'
    )
    instructions = lambda text: [
        rate.sub("", line) for line in text.splitlines()
        if re.match(r"\s*(?:ROOT )?%", line)
    ]
    assert "constant(0.0009995)" in text and "constant(4096)" in probe
    # (4,898 instructions since PR 49 took the projections' replay out of
    # the program; over 5,000 before)
    assert len(instructions(text)) > 4500
    assert instructions(text) == instructions(probe)


#: sha256 of the instructions of ``train_ling3_t8192_b2``'s KDA expert layer
#: (its pattern's layer 5 alone, 2 x 8,192 tokens, under ``remat``) compiled
#: for the described chip ON THE PARENT of PR 48 (42dead0), without what is
#: the file's and not the program's (an op's metadata; a kernel's body, which
#: carries the line numbers of the file that built it: ``tests/
#: test_solar2.py`` holds the kernels' own jaxprs to the parent's).  PR 48
#: edits the four files this layer runs (``ops/kda.py``, ``ops/pallas/
#: kda.py``, ``ops/pallas/kda_mixer.py``, ``_kda_partial``, which PR 56 moved
#: to ``models/mixers/kda.py`` with this digest untouched) for a gate
#: without a bound; the bounded gate is chosen statically, so this program
#: is the parent's.  Re-recorded in PR 49 ON ITS OWN TREE, by intent: under
#: ``remat`` the layer keeps the mixer's five bf16 projections by name, so the
#: backward's replay of their matmuls is gone from the program (4,354
#: instructions -> 4,212; PR 48's parent read 54269a1a...f76e27, and PR 49's
#: parent, a202f4f, still did).  The rehearsal's digests of ``tests/
#: test_shared_step_text.py`` (``remat`` off) did not move.  Since PR 53 the
#: VMEM a kernel's body USED (``used_scoped_memory_configs``) is stripped with
#: the body it belongs to: ``kda_fwd``'s went 5,369,856 -> 5,677,056 bytes
#: with its inverse and nothing else of the layer moved: PR 53's parent
#: (700e969, which read 648af705...554a2dc1 with that field in) and its own
#: tree both read the digest below.
LING3_KDA_LAYER = "91dd79c3460ec6b4f3fabfed40a01d757676585ddb5caabd04eee2a4e1c433c4"


def test_ling3_kda_layer_is_the_parents_program(v5e, monkeypatch):
    import hashlib

    text = _step_text("train_ling3_t8192_b2", 1, v5e, monkeypatch, layers=(5,))
    assert "kda_fwd" in text and "kda_bwd" in text and "kda_decay_fwd" in text
    strip = re.compile(
        r', metadata=\{[^}]*\}|"body":"[^"]*"|"used_scoped_memory_configs":\[[^\]]*\]'
    )
    lines = [
        strip.sub("", line) for line in text.splitlines()
        if re.match(r"\s*(?:ROOT )?%", line)
    ]
    assert len(lines) > 4000
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest() == LING3_KDA_LAYER


def test_kda_kernels_compile_under_the_unbounded_gate(one_chip):
    """``train_solar2_t8192_b1``'s KDA core alone, forward and gradient, for
    any ``g <= 0`` (the split by halving: sublane rolls, seven masked
    products, the inverse by halving): 64 head-sequences of 8,192 tokens,
    ``dk = dv = 128``; and its softplus decay chain, a bf16 projection of
    8,192 x 8,192."""
    from accl_tpu.ops.pallas import kda, kda_mixer

    B, H, T, d = 1, 64, 8192, 128
    rows = jax.ShapeDtypeStruct((B, H, T, d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((B, H, T), jnp.float32, sharding=one_chip)
    core = lambda *a: kda.kda(*a, safe=True, interpret=False)
    text = jax.jit(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2, 3, 4)
    )).lower(rows, rows, rows, rows, beta).compile().as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    _chain_compiles(
        "kda_decay", lambda *a: kda_mixer.decay_in(*a, None, interpret=False),
        [((B, T, H * d), jnp.bfloat16), ((H * d,), jnp.float32),
         ((H,), jnp.float32)],
        one_chip,
    )


def test_kda_kernels_compile_at_olmoh_padded_shapes(one_chip):
    """``train_olmoh_t8192_b1``'s delta core alone as ``_decay_a_head`` hands
    it over, forward and gradient, for any ``g <= 0``: 30 head-sequences of
    8,192 tokens, keys padded to 128 columns beside values padded to 256."""
    from accl_tpu.ops.pallas import kda

    B, H, T, dk, dv = 1, 30, 8192, 128, 256
    struct = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    args = [struct(B, H, T, dk)] * 2 + [struct(B, H, T, dv), struct(B, H, T, dk), struct(B, H, T)]
    assert kda.takes(args[0].shape, args[2].shape) and kda.takes_padded(96, 192)
    core = lambda *a: kda.kda(*a, safe=True, interpret=False)
    text = jax.jit(jax.grad(
        lambda *a: core(*a).sum(), argnums=(0, 1, 2, 3, 4)
    )).lower(*args).compile().as_text()
    assert "kda_fwd" in text and "kda_bwd" in text


def test_olmoh_step_takes_the_kernels_at_padded_heads(v5e, monkeypatch):
    """One Gated DeltaNet layer and the full-attention layer of the cell's
    four, 1 x 8,192 tokens of the whole 100,352 ids, under ``remat`` as the
    cell runs: the delta core at a decay a head and heads of 96 / 192 is
    ``kda_fwd`` (twice: the forward and its replay) and ``kda_bwd`` under
    ``accl.attn::kda`` (0 would mean that the shape rule fell through to the
    XLA form, whose scan is a loop: nothing of the scope is in one), on
    operands padded to 128 / 256; the chains round it are XLA's under
    ``accl.attn::kda_proj`` (no chain kernel takes such heads), and the four
    wide bf16 projections they read (q, k, v, the output gate) are
    multiplied out ONCE, kept under ``remat`` by name; the full layer's core
    the flash kernels at 30 heads under ``accl.attn::core``, each ONCE
    (``_flash_once``), its projections under ``accl.attn::gqa_proj``; and no
    array larger than the float32 logits (8,192 x 100,352: memory linear in
    T)."""
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3

    compiled = _step("train_olmoh_t8192_b1", 2, v5e, monkeypatch, layers=(2, 3))
    text = compiled.as_text()
    entry = scope_ops.scopes_of(text)
    every = train_steps_ling3.scoped_instructions(text)
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::core",
                  "accl.attn::gqa_proj", "accl.embed::grad"):
        assert entry.get(scope), scope
    core, chains = entry["accl.attn::kda"], entry["accl.attn::kda_proj"]
    assert sum(n.startswith("kda_fwd") for n in core) == 2
    assert sum(n.startswith("kda_bwd") for n in core) == 1
    assert not any(n.startswith("while") for n in core)
    assert set(core) == set(every["accl.attn::kda"])
    assert re.search(r"f32\[30,8192,256\]", text)       # v and o, padded
    assert not any(n.startswith("kda_in_") or n.startswith("kda_out_")
                   for n in chains)
    # q and k once each, v once, the gate once (the compiler writes it
    # without the batch's 1) beside ``wo``'s cotangent, the same shape: a
    # replay would make each forward product twice
    assert len(_products(text, chains, "bf16[1,8192,2880]")) == 2
    assert len(_products(text, chains, "bf16[1,8192,5760]")) == 1
    assert len(_products(text, chains, "bf16[8192,5760]")) == 1 + 1
    flash = entry["accl.attn::core"]
    _flash_once(flash)
    assert re.search(r"bf16\[1,30,8192,128\]", text)
    sizes = sorted({
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    })
    assert sizes[-1] == 8192 * 100352
    assert not re.search(r"\[8192,8192,\d+\]|\[30,8192,8192\]", text)
    # the whole cell, four layers: 11,371,662,336 bytes of scratch (my
    # compile for the described chip, PR 52); this cut has half the layers
    assert compiled.memory_analysis().temp_size_in_bytes <= 11_371_662_336


def test_mimo_step_takes_the_flash_kernels_in_both_kinds(v5e, monkeypatch):
    """One sliding and one full layer of the MiMo-V2.5 cell's seven, 1 x
    8,192 tokens, under ``remat`` as the cell runs (the rule fixed in ISSUE
    54): the cores are ``flash_fwd`` (ONCE a layer: ``_flash_once``) and
    ``flash_bwd`` under ``accl.attn::window`` (a window of 128 keys in tiles
    of 512, the sink a head an operand in SMEM) and under ``accl.attn::core``,
    at 64 heads x 8,192 x (128 + a rotating 64 | 128) on 8 and 4 KV heads, the
    rotating key part on the KV heads and never expanded; the projections
    under ``accl.attn::gqa_proj``; and no array larger than the float32
    logits (8,192 x 19,072: nothing square in the length, no head of 192
    padded to 256)."""
    from perfbench import scope_ops

    compiled = _step("train_mimo_t8192_b1", 2, v5e, monkeypatch, layers=(1, 6))
    text = compiled.as_text()
    entry = scope_ops.scopes_of(text)
    for scope in ("accl.attn::window", "accl.attn::core",
                  "accl.attn::gqa_proj", "accl.moe::experts"):
        assert entry.get(scope), scope
    for scope in ("accl.attn::window", "accl.attn::core"):
        _flash_once(entry[scope])
    # q's and k's products ONCE a layer, written head-major as the core takes
    # them (kept under ``remat`` by name since PR 55; the parent's text has
    # each twice): q of both layers, k on the sliding layer's 8 K/V heads and
    # on the full layer's 4
    proj = entry["accl.attn::gqa_proj"]
    assert len(_products(text, proj, "bf16[1,64,8192,192]")) == 2
    assert len(_products(text, proj, "bf16[1,8,8192,192]")) == 1
    assert len(_products(text, proj, "bf16[1,4,8192,192]")) == 1
    assert re.search(r"bf16\[1,64,8192,128\]", text)      # q without position
    assert re.search(r"bf16\[1,8,8192,64\]", text)        # the rotating key part
    assert re.search(r"bf16\[1,4,8192,64\]", text)
    assert not re.search(r"bf16\[1,64,8192,256\]|\[64,8192,8192\]", text)
    sizes = sorted({
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    })
    assert sizes[-1] <= 8192 * 19072
    # the whole cell, seven layers: 3,315,502,080 bytes of scratch (my
    # compile for the described chip, PR 54); this cut has two of them
    assert compiled.memory_analysis().temp_size_in_bytes <= 3_315_502_080


def test_solar2_step_takes_the_kernels_under_the_unbounded_gate(
    v5e, monkeypatch
):
    """The GQA layer and one KDA layer of the cell's four, 1 x 8,192 tokens,
    under ``remat`` as the cell runs: the KDA core is ``kda_fwd`` (twice:
    the forward and its replay) and ``kda_bwd`` under ``accl.attn::kda``,
    nothing of it in a loop's body; the chains round it the kernels of
    ``kda_mixer`` under ``accl.attn::kda_proj``, the decay's in its
    softplus form, and the five bf16 projections they read (q, k, v and the
    two gates' FINAL products out of rank 128) multiplied out ONCE, kept
    under ``remat`` by name (PR 49); the softmax layer's core the flash kernels under
    ``accl.attn::core``, its projections, gate and ``wo`` under
    ``accl.attn::gqa_proj``; the held rows, 4,096 wide in a buffer of
    16,384 (134 MB: past what ``_gathers_win`` calls near), placed by the
    ``place_rows`` kernel; the grouped matmuls the three kernels; and no
    array a square of the length (memory linear in T): the largest are a
    held bank's matrix and the float32 logits."""
    from accl_tpu.models import moe
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3

    assert not moe._gathers_win(16384, 8192 * 8, 4096, 2)
    compiled = _step("train_solar2_t8192_b1", 2, v5e, monkeypatch, layers=(0, 1))
    text = compiled.as_text()
    entry = scope_ops.scopes_of(text)
    every = train_steps_ling3.scoped_instructions(text)
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::core",
                  "accl.attn::gqa_proj", "accl.moe::route", "accl.moe::dispatch",
                  "accl.moe::experts", "accl.moe::combine", "accl.moe::shared"):
        assert entry.get(scope), scope
    core, chains = entry["accl.attn::kda"], entry["accl.attn::kda_proj"]
    assert sum(n.startswith("kda_fwd") for n in core) == 2
    assert sum(n.startswith("kda_bwd") for n in core) == 1
    assert not any(n.startswith("while") for n in core)
    assert set(core) == set(every["accl.attn::kda"])
    kernels = {
        "kda_in_fwd": 6, "kda_in_bwd": 3, "kda_decay_fwd": 2,
        "kda_decay_bwd": 1, "kda_out_fwd": 2, "kda_out_bwd": 1,
    }
    for kernel, count in kernels.items():
        assert sum(n.startswith(kernel) for n in chains) == count, kernel
    # the five named projections once each (the parent of PR 49: twice) and
    # ``wo``'s cotangent, the same shape
    assert len(_products(text, chains, "bf16[1,8192,8192]")) == 5 + 1
    _flash_once(entry["accl.attn::core"])
    for kernel in ("gmm_fwd", "gmm_dlhs", "gmm_drhs"):
        assert any(kernel in n for n in entry["accl.moe::experts"]), kernel
    assert re.search(r"%place_rows\S* = bf16\[8192,4096\]", text)
    assert re.search(r"bf16\[16384,4096\]", text)
    sizes = sorted({
        int(np.prod([int(n) for n in dims.split(",")]))
        for dims in re.findall(r"\b(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    })
    # one head's scores over the whole length would be 2^26 elements a head
    # and 2^32 for the 64: nothing here is; the largest arrays are a held
    # bank's matrix of 40 x 4,096 x 1,280 (ISSUE 48 expected the float32
    # logits' 8,192 x 24,576, which come next, 4% smaller)
    assert sizes[-1] == 40 * 4096 * 1280 and sizes[-2] == 8192 * 24576
    assert not re.search(r"\[8192,8192,\d+\]|\[64,8192,8192\]", text)
    # the whole cell, four layers: 6,332,889,600 bytes of scratch (my compile
    # for the described chip, PR 55; 4,686,238,208 at PR 48, 6,029,447,680
    # since PR 49 kept the three KDA layers' projections); this cut has half
    # the layers
    assert compiled.memory_analysis().temp_size_in_bytes <= 6_332_889_600
    # and no more than PR 49's parent at this cut (4,527,492,608) with the five
    # kept projections of the one KDA layer, 5 x 8,192 x 8,192 x 2 bytes, and
    # what the GQA layer keeps since PR 55: q and o, 8,192 x 8,192 x 2 bytes
    # each, k and v on 8 of the 64 heads, one float32 logsumexp a row a head
    # (this cut read 4,527,686,144 at PR 49 and reads 4,831,128,064)
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        4_527_492_608 + 671_088_640
        + 2 * 134_217_728 + 2 * 16_777_216 + 2_097_152
    )
