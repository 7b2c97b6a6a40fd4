"""Mixture-of-Experts FFN with expert parallelism over the device mesh.

Expert parallelism (ep) is the fourth first-class sharding axis of the
flagship model family (dp x tp x sp x ep): experts live sharded across the
``ep`` mesh axis and tokens travel to their expert's chip through the
framework's all-to-all — the dispatch/combine pattern whose communication
substrate is exactly the reference's fused ``all_to_all``
(ccl_offload_control.c:2123-2218); here it rides ICI via
``accl_tpu.ops.collectives.alltoall``'s lowering (or the Pallas
one-sided-write kernel when composed manually).

:func:`moe_ffn` routes every token to its top-k experts (any k) and has
two dispatches:

* **fixed capacity** (``capacity_factor`` a number): each expert takes at
  most ``capacity_factor * N * k / E`` routing entries, so the whole layer
  is static-shaped; entries past capacity fall through the residual path
  (the Switch-Transformer formulation).  Every multi-chip path uses it:
  the ``(E, cap, D)`` buffer is what the fixed-count all-to-all carries.
* **dropless** (``capacity_factor=None``): the N*k routing entries are
  sorted by expert and the experts run as one grouped matmul over the
  sorted rows with per-expert group sizes
  (``ops.pallas.grouped_matmul``: tiled Pallas kernels after jax's
  megablox, one each for the forward, the input's and the weights'
  gradient, tiles from the shapes; interpreted off the TPU like every
  kernel of that tier), then unsorted and combined.  No entry is ever
  dropped and no buffer scales with E.  One chip's experts only: across
  an expert axis the exchange needs a different count a peer (ROADMAP
  R2(b)).

The dropless dispatch also computes what lies beyond softmax top-k, each
picked by what the layer's parameters hold or by the caller's word:

* ``router="sigmoid"``: float32 sigmoid scores; selection on ``score +
  bias`` where the bank has a ``bias`` (state: read through
  ``stop_gradient``, moved by the train step's rule and by no gradient),
  weights from the UNBIASED scores of the chosen, renormalised over them
  (``/ (sum + 1e-20)``) and times ``route_scale``.  No auxiliary term.
* a ``shared`` expert in the parameters: one dense gated-SiLU FFN every
  token passes through, added to the routed result (scope
  ``accl.moe::shared``).
* HELD EXPERTS: a ``gate`` wider than the bank.  The router keeps all its
  outputs and its top-k; the bank's ``E`` matrices are experts
  ``first_expert .. first_expert + E`` of them (one chip's share of an
  expert-parallel group).  Entries whose expert is not held are left out
  of the sort's front, the gathers and the grouped matmuls (group sizes
  over the held experts only), and what they would have added is left
  out of the result; no code stands in for the absent chips or their
  exchange.  The held entries' rows have a static buffer
  (``held_row_factor`` times the balanced share, rounded up to the row
  tile); an entry past it is dropped and counted, as a receive buffer of
  the exchange would.  A buffer row's result is placed on its token,
  weighted, and so is its cotangent on the way back
  (:func:`_place_rows`): by k gathers a token through the inverse of the
  sort, as the dropless path combines, or where the shapes make that the
  dearer (:func:`_gathers_win`) by one scatter-add of the rows.  The
  layer counts tokens an expert over ALL the router's experts, the
  entries held here, and those dropped.

* GROUP-LIMITED top-k (``n_group`` > 1, the softmax router; DeepSeek-V2's
  ``group_limited_greedy``): the router's experts are ``n_group`` groups
  of consecutive experts (the devices of its expert-parallel group), a
  group's score is the LARGEST of its experts' probabilities, the
  ``topk_group`` best groups stay and the top-k is over what stays; the
  weights are the chosen probabilities, times ``route_scale``.  With a
  held share of whole groups an entry can be held only where its group
  was kept.
* the BALANCE LOSSES (``balance_groups`` > 0; :func:`balance_losses`):
  DeepSeek-V2's expert-, device- and communication-level terms, each a
  sequence's, averaged over the batch; whole on every chip, since the
  router scores all of its experts everywhere.

Experts are two-matrix GELU FFNs (``w1``, ``w2``) or, with a ``w3`` in
the parameters, gated-SiLU FFNs ``(silu(x w1) * (x w3)) w2``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas.grouped_matmul import ROWS, grouped_matmul
from ..utils.profiling import device_scope


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32, gated: bool = False,
                    router_experts: int | None = None,
                    shared_d_ff: int = 0, bias: bool = False):
    """Gate + per-expert FFN weights (unsharded; shard E over 'ep').
    ``gated`` adds ``w3``, the second up-projection of a gated-SiLU
    expert.  ``router_experts``: the gate's width where the bank holds
    only ``n_experts`` of them; ``shared_d_ff``: a ``shared`` gated-SiLU
    expert of that width; ``bias``: a float32 selection bias, zero."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale = d_model ** -0.5
    n_router = n_experts if router_experts is None else router_experts
    params = {
        "gate": jax.random.normal(k1, (d_model, n_router), dtype) * scale,
        "w1": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * scale,
        "w2": jax.random.normal(k3, (n_experts, d_ff, d_model), dtype)
        * (d_ff ** -0.5),
    }
    if gated:
        params["w3"] = jax.random.normal(
            jax.random.fold_in(k2, 1), (n_experts, d_model, d_ff), dtype
        ) * scale
    if bias:
        params["bias"] = jnp.zeros((n_router,), jnp.float32)
    if shared_d_ff:
        ks = jax.random.split(jax.random.fold_in(key, 7), 3)
        params["shared"] = {
            "w1": jax.random.normal(ks[0], (d_model, shared_d_ff), dtype) * scale,
            "w3": jax.random.normal(ks[1], (d_model, shared_d_ff), dtype) * scale,
            "w2": jax.random.normal(ks[2], (shared_d_ff, d_model), dtype)
            * (shared_d_ff ** -0.5),
        }
    return params


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, idx, back, k: int):
    """``x[idx // k]``: each row of ``x`` appears ``k`` times in the
    result (a permutation at k=1).  ``back`` is the inverse of the
    permutation ``idx``, so the cotangent is a gather and a sum of k
    rows too — never a scatter-add over duplicate rows."""
    return x[idx // k]


def _take_rows_fwd(x, idx, back, k):
    return x[idx // k], (back, x.shape[0])


def _take_rows_bwd(k, res, g):
    back, rows = res
    g = g[back].reshape(rows, k, g.shape[-1])
    return g.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def balance_losses(probs, topk_e, seqs: int, n_group: int, topk_group: int):
    """DeepSeek-V2's three balance losses of one layer, unweighted:
    ``probs`` (N, E) the router's float32 probabilities, ``topk_e`` (N, k)
    the chosen experts, ``N = seqs * T`` tokens in ``seqs`` sequences,
    ``n_group`` groups ``E_d`` of consecutive experts, of which a token
    may reach ``topk_group`` (= ``n_group`` where routing has no limit).
    For each sequence, with ``c_e`` its entries sent to expert ``e``,
    ``f_e = E / (k T) c_e``, ``P_e`` its tokens' mean ``probs[:, e]`` and
    ``n_d`` its tokens with at least one entry in group ``d``:

    * ``expert``: ``sum_e f_e P_e``;
    * ``device``: ``sum_d (mean_{e in E_d} f_e) (sum_{e in E_d} P_e)``;
    * ``comm``:   ``sum_d n_group / (topk_group T) n_d (sum_{e in E_d} P_e)``;

    then the mean over the sequences.  The counts carry no gradient; the
    gradient is ``P``'s."""
    N, E = probs.shape
    k = topk_e.shape[-1]
    T = N // seqs
    hit = jax.nn.one_hot(topk_e, E, dtype=jnp.float32).sum(axis=1)  # (N, E)
    hit = lax.stop_gradient(hit).reshape(seqs, T, n_group, E // n_group)
    f = hit.sum(axis=1) * (E / (k * T))                     # (S, G, E/G)
    P = probs.reshape(seqs, T, n_group, E // n_group).mean(axis=1)
    n = (hit.sum(axis=-1) > 0).astype(jnp.float32).sum(axis=1)   # (S, G)
    group_p = P.sum(axis=-1)                                # (S, G)
    return {
        "balance_expert": jnp.mean(jnp.sum(f * P, axis=(1, 2))),
        "balance_device": jnp.mean(jnp.sum(f.mean(axis=-1) * group_p, axis=1)),
        "balance_comm": jnp.mean(jnp.sum(
            n * (n_group / (topk_group * T)) * group_p, axis=1
        )),
    }


def _expert_act(up, params, matmul):
    """The experts' hidden activation from the first up-projection:
    gated SiLU where the bank has a ``w3``, GELU otherwise."""
    if "w3" in params:
        return jax.nn.silu(up) * matmul(params["w3"])
    return jax.nn.gelu(up)


def _gathers_win(rows: int, entries: int, D: int, itemsize: int) -> bool:
    """Whether :func:`_place_rows` is cheaper as ``entries`` gathered rows
    than as one scatter-add of ``rows`` rows of ``D`` columns, by what
    XLA's two ops were measured to move on a v5e (``PERF.md`` section 5,
    PR 35): a gather reads its rows at about 300 GB/s while the array it
    reads from is small enough for the compiler to keep in the chip's
    near memory (96 MiB was, 128 MiB was not) and at about 100 GB/s from
    HBM; a scatter-add takes a float32 row at 80 GB/s at best (up to
    4,096 columns; past them it was 1.4 to 15 times slower).  The choice
    is the compiler's weakness at a shape, so it reads shapes and nothing
    else."""
    gather_gbs = 300 if rows * D * itemsize <= 96 << 20 else 100
    return entries * D * itemsize / gather_gbs < rows * D * 4 / 80


def _place_rows(rows, order, slot, p=None):
    """Each buffer row on its token: ``y[t] = sum_j p[t, j] * rows[slot[t,
    j]]`` over the ``(N, k)`` slots, a slot past the last row reading
    zeros and no ``p`` a weight of 1; products and sums in float32, the
    result in ``rows``' type.  ``order`` is the entries in buffer order,
    the inverse of ``slot``.  One of two lowerings, by
    :func:`_gathers_win`: k gathers of a row a token through ``slot``, or
    one scatter-add of the rows through ``order`` (a row no entry owns
    must then be zero, as the caller's masks leave it).  They differ in
    the order of a token's at most k float32 additions."""
    N, k = slot.shape
    R, D = rows.shape
    if _gathers_win(R, N * k, D, rows.dtype.itemsize):
        acc = 0.0
        for j in range(k):
            got = rows.at[slot[:, j]].get(mode="fill", fill_value=0)
            got = got.astype(jnp.float32)
            acc = acc + (got if p is None else got * p[:, j, None])
    else:
        vals = rows.astype(jnp.float32)
        if p is not None:
            vals = vals * p.reshape(-1)[order][:, None]
        acc = jnp.zeros((N, D), jnp.float32).at[order // k].add(vals)
    return acc.astype(rows.dtype)


@jax.custom_vjp
def _held_rows(x, order, slot):
    """``x[order // k]``, the buffer's rows; the cotangent is
    :func:`_place_rows` of the rows' cotangents."""
    return x[order // slot.shape[-1]]


def _held_rows_fwd(x, order, slot):
    return _held_rows(x, order, slot), (order, slot)


def _held_rows_bwd(res, g):
    return _place_rows(g, *res), None, None


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


@jax.custom_vjp
def _combine_held(out, p, order, slot):
    """:func:`_place_rows` of the experts' results, weighted by the
    chosen probabilities ``p`` ``(N, k)``.  The cotangents are gathers
    of the result's by ``order``, as the rows were gathered: ``out``'s in
    its type, ``p``'s a buffer row's dot set down at its entry."""
    return _place_rows(out, order, slot, p)


def _combine_held_fwd(out, p, order, slot):
    return _combine_held(out, p, order, slot), (out, p, order)


def _combine_held_bwd(res, g):
    out, p, order = res
    g = g[order // p.shape[-1]].astype(jnp.float32)
    dots = jnp.sum(g * out.astype(jnp.float32), axis=-1)
    # the rows' scalars scattered, each entry once: N*k scalars gathered
    # through the slots cost XLA 1.1 ms a layer in the Trinity cell
    d_p = jnp.zeros((p.size,), dots.dtype).at[order].add(
        dots, unique_indices=True
    )
    return (
        (g * p.reshape(-1)[order][:, None]).astype(out.dtype),
        d_p.reshape(p.shape), None, None,
    )


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def _expert_bank(rows, params, sizes):
    """``rows``, sorted by expert in groups of ``sizes``, through the
    bank's grouped matmuls."""
    with device_scope("accl.moe::experts"):
        grouped = lambda a, w: grouped_matmul(a, w, sizes)
        h = _expert_act(
            grouped(rows, params["w1"]), params, partial(grouped, rows)
        )
        return grouped(h, params["w2"])


def held_rows(entries: int, held: int, of: int, factor: float) -> int:
    """Rows of the held entries' buffer: ``factor`` times the balanced
    share ``entries * held / of``, rounded up to the grouped matmul's
    row tile (to 16 rows, a bf16 tile's sublanes, where it is less than
    one), and never more than every entry."""
    want = -(-int(factor * entries * held) // of)
    tile = ROWS if want >= ROWS else 16
    return min(-(-want // tile) * tile, entries)


def _held_experts(flat, params, topk_e, topk_p, tp_axis, first: int,
                  rows: int):
    """The held experts' part of the result (module docstring): the
    bank is experts ``first .. first + E`` of the router's.  Entries are
    sorted with the held ones in front, by expert; the first ``rows`` of
    the order are gathered and run through the grouped matmuls with the
    held experts' group sizes (clipped to the buffer), and each row's
    result is placed on its token, weighted (:func:`_place_rows`).  An
    entry's place in the order says whether it has a row: it does iff
    the place is under ``kept``, so ``slot`` is the place or, past the
    buffer, no row.  The kernels visit only the tiles the groups reach,
    so what lies past them in the buffer is never written: it is masked
    on the way in (for the cotangent) and on the way out.  Returns ``(y,
    counters)``: ``expert_tokens`` over the router's experts,
    ``held_entries`` and ``dropped``."""
    N, D = flat.shape
    k = topk_e.shape[-1]
    E = params["w1"].shape[0]
    n_router = params["gate"].shape[1]
    with device_scope("accl.moe::dispatch"):
        expert = topk_e.reshape(-1)                       # (N*k,) entries
        counts = jnp.zeros((n_router,), jnp.int32).at[expert].add(1)
        local = expert - first
        key = jnp.where((local >= 0) & (local < E), local, E)
        every = jnp.argsort(key, stable=True)             # held first
        back = jnp.argsort(every)                         # entry -> place
        order = every[:rows]
        ends = jnp.minimum(jnp.cumsum(counts[first:first + E]), rows)
        sizes = jnp.diff(ends, prepend=0)
        held, kept = jnp.sum(counts[first:first + E]), ends[-1]
        valid = jnp.arange(rows) < kept
        slot = jnp.where(back < kept, back, rows).reshape(N, k)
        x = jnp.where(valid[:, None], _held_rows(flat, order, slot), 0)
    out = _expert_bank(x, params, sizes)                  # (rows, D)
    with device_scope("accl.moe::combine"):
        out = jnp.where(valid[:, None], out, 0)
        # inside a shard_map: the weights varying over the axes the rows
        # vary over (tp-sharded experts), so that their cotangent is
        # summed over those by the cast's transpose
        p = topk_p
        if missing := tuple(jax.typeof(out).vma - jax.typeof(p).vma):
            p = lax.pcast(p, missing, to="varying")
        y = _combine_held(out, p, order, slot)
        if tp_axis is not None:
            y = lax.psum(y, tp_axis)
    return y, {
        "expert_tokens": counts, "held_entries": held, "dropped": held - kept,
    }


def _dropless_experts(flat, params, topk_e, topk_p, tp_axis):
    """Every routing entry through its expert, none dropped: sort the
    N*k entries by expert, grouped matmuls over the sorted rows, unsort,
    weight and sum a token's k results.  Returns ``(y, tokens an
    expert)``."""
    N, D = flat.shape
    k = topk_e.shape[-1]
    E = params["w1"].shape[0]
    with device_scope("accl.moe::dispatch"):
        expert = topk_e.reshape(-1)                       # (N*k,) entries
        order = jnp.argsort(expert, stable=True)          # sorted -> entry
        back = jnp.argsort(order)                         # entry -> sorted
        sizes = jnp.zeros((E,), jnp.int32).at[expert].add(1)
        rows = _take_rows(flat, order, back, k)           # (N*k, D) by expert
    out = _expert_bank(rows, params, sizes)               # (N*k, D)
    with device_scope("accl.moe::combine"):
        got = _take_rows(out, back, order, 1).reshape(N, k, D)
        y = (got.astype(jnp.float32) * topk_p[..., None]).sum(axis=1)
        y = y.astype(flat.dtype)
        if tp_axis is not None:
            y = lax.psum(y, tp_axis)
    return y, sizes


def moe_ffn(
    x: jax.Array,
    params: dict,
    ep_axis: str | None = None,
    capacity_factor: float | None = 1.5,
    k: int = 1,
    return_aux: bool = False,
    tp_axis: str | None = None,
    renormalize: bool = True,
    router: str = "softmax",
    route_scale: float = 1.0,
    first_expert: int = 0,
    held_row_factor: float = 2.0,
    n_group: int = 1,
    topk_group: int = 1,
    balance_groups: int = 0,
    switch_balance: bool = False,
):
    """Top-k gated MoE FFN (k=1 is Switch routing, k=2 the classic MoE).

    ``x``: (B, T, D) local tokens.  Without ``ep_axis``: every expert is
    local (single-device reference semantics).  With ``ep_axis`` (inside
    shard_map): ``params['w1']/['w2']`` are the LOCAL expert shards
    (E_local = E/ep leading dim) while ``params['gate']`` is replicated;
    dispatch and combine are all-to-alls over the axis.

    Each token routes to its top-k experts with the gate probabilities
    renormalized over the chosen k (``renormalize=False`` keeps the raw
    softmax probabilities, a published ``norm_topk_prob: false``); every
    (token, choice) pair is an independent routing entry through the same
    dispatch, so the layer stays static-shaped for any k.

    ``capacity_factor=None`` is the DROPLESS dispatch (module docstring):
    the router's matmul accumulates and its softmax runs in float32, the
    entries are sorted by expert and run through grouped matmuls, and no
    entry is dropped whatever the routing.  It has no ``ep_axis`` form.
    ``router``, ``route_scale``, ``first_expert`` and ``held_row_factor``
    are this dispatch's (module docstring: the sigmoid router, the shared
    expert, held experts); with them ``return_aux`` adds ``held_entries``
    and its ``load_balance`` and ``router_z`` are zero.  So are
    ``n_group`` / ``topk_group`` (group-limited top-k on the softmax
    router; 1, 1 is plain top-k) and ``balance_groups``: where it is not
    0, ``return_aux`` adds :func:`balance_losses` over that many groups,
    a sequence a row of ``x``, held share or not.  ``switch_balance``
    (the softmax router): ``load_balance`` is the Switch term all the
    same, over ALL the router's outputs and this chip's rows, whatever
    share of the experts is held (Qwen3-MoE's auxiliary loss a layer).

    Returns (B, T, D): expert outputs weighted by the gate probability;
    over-capacity entries contribute zero (callers add the residual).

    ``tp_axis``: tensor parallelism WITHIN each expert — ``w1``/``w2``
    carry the d_ff dim tp-sharded (column/row-parallel per expert, the
    Megatron split), and the expert outputs are partial sums allreduced
    over tp after the combine.  Routing uses the replicated gate, so
    every tp peer dispatches identically and the FFN FLOPs/weights
    shard by the tp factor instead of replicating.

    ``return_aux=True`` additionally returns the router health terms
    computed over THIS rank's tokens (average across dp/ep in the loss):

    * ``load_balance`` — the Switch-Transformer auxiliary,
      ``E * sum_e f_e * P_e`` (f = dispatch fraction, P = mean router
      probability): 1.0 at perfect balance, up to E when the router
      collapses onto one expert; add ``~0.01 * load_balance`` to the
      loss to keep experts utilized.
    * ``router_z`` — the ST-MoE z-loss, ``mean(logsumexp(logits)^2)``,
      which keeps router logits small/stable in bf16.

    and two counters: ``expert_tokens`` (E,), the routing entries sent to
    each expert, and ``dropped``, the entries past capacity (0 when
    dropless); under group-limited routing also ``group_tokens``
    (n_group,), the tokens whose kept groups include each group.
    """
    B, T, D = x.shape
    N = B * T
    flat = x.reshape(N, D)

    ep = 1 if ep_axis is None else lax.axis_size(ep_axis)
    e_local = params["w1"].shape[0]
    E = e_local * ep  # global expert count
    n_router = params["gate"].shape[1]

    beyond = (
        router != "softmax" or "shared" in params or n_router != E
        or n_group > 1 or route_scale != 1.0 or bool(balance_groups)
    )
    if beyond and capacity_factor is not None:
        raise ValueError(
            "the sigmoid router, a shared expert, held experts, grouped "
            "top-k, a route scale and the balance losses are the "
            "dropless dispatch's (capacity_factor=None)"
        )
    if capacity_factor is None:
        if ep > 1:
            raise NotImplementedError(
                "dropless routing (capacity_factor=None) over an expert "
                f"axis of {ep}: the exchange needs a different count a "
                "peer, which the fixed-count all-to-all does not carry "
                "(ROADMAP R2(b)); give a capacity_factor or keep every "
                "expert on the chip"
            )
        with device_scope("accl.moe::route"):
            logits = jnp.dot(
                flat, params["gate"], preferred_element_type=jnp.float32
            )
            if router == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                pick = scores
                if "bias" in params:
                    pick = scores + lax.stop_gradient(params["bias"])
                _, topk_e = lax.top_k(pick, k)
                topk_p = jnp.take_along_axis(scores, topk_e, axis=-1)
                if renormalize:
                    topk_p = topk_p / (
                        jnp.sum(topk_p, axis=-1, keepdims=True) + 1e-20
                    )
                if route_scale != 1.0:
                    topk_p = topk_p * route_scale
            elif router != "softmax":
                raise ValueError(f"unknown router {router!r}")
            else:
                probs = jax.nn.softmax(logits, axis=-1)
                pick = probs
                if n_group > 1:
                    # a group's score is its best expert's; what is not in
                    # one of the topk_group best groups scores 0
                    best = probs.reshape(N, n_group, -1).max(axis=-1)
                    _, keep = lax.top_k(best, topk_group)
                    kept = jax.nn.one_hot(
                        keep, n_group, dtype=probs.dtype
                    ).sum(axis=1)                         # (N, n_group) 1/0
                    pick = probs * jnp.repeat(
                        kept, n_router // n_group, axis=-1
                    )
                topk_p, topk_e = lax.top_k(pick, k)
                if k > 1 and renormalize:  # as below: k=1 keeps the raw prob
                    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
                if route_scale != 1.0:
                    topk_p = topk_p * route_scale
        if n_router != E:
            rows = held_rows(N * k, E, n_router, held_row_factor)
            y, counters = _held_experts(
                flat, params, topk_e, topk_p, tp_axis, first_expert, rows
            )
        else:
            y, sizes = _dropless_experts(flat, params, topk_e, topk_p, tp_axis)
            counters = {
                "expert_tokens": sizes, "dropped": jnp.zeros((), jnp.int32),
            }
        if "shared" in params:
            with device_scope("accl.moe::shared"):
                sp = params["shared"]
                every = (
                    jax.nn.silu(flat @ sp["w1"]) * (flat @ sp["w3"])
                ) @ sp["w2"]
                if tp_axis is not None:
                    every = lax.psum(every, tp_axis)
            y = y + every
        y = y.reshape(B, T, D)
        if not return_aux:
            return y
        if n_group > 1:
            # tokens whose kept groups include each group
            counters["group_tokens"] = kept.sum(axis=0).astype(jnp.int32)
        if balance_groups:
            if router != "softmax":
                raise ValueError("the balance losses are the softmax router's")
            with device_scope("accl.moe::route"):
                counters.update(balance_losses(
                    probs, topk_e, B, balance_groups,
                    topk_group if n_group > 1 else balance_groups,
                ))
        if beyond:
            load_balance = jnp.zeros((), jnp.float32)
            if switch_balance:
                if router != "softmax":
                    raise ValueError(
                        "the Switch load-balance term is the softmax router's"
                    )
                with device_scope("accl.moe::route"):
                    f = counters["expert_tokens"].astype(jnp.float32) / (N * k)
                    load_balance = n_router * jnp.sum(f * probs.mean(axis=0))
            return y, {
                "load_balance": load_balance,
                "router_z": jnp.zeros((), jnp.float32), **counters,
            }
        f = sizes.astype(jnp.float32) / (N * k)
        return y, {
            "load_balance": E * jnp.sum(f * probs.mean(axis=0)),
            "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "expert_tokens": sizes,
            "dropped": jnp.zeros((), jnp.int32),
        }

    # --- routing (replicated math: identical on every member rank) -------
    logits = flat @ params["gate"]  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = lax.top_k(probs, k)  # (N, k)
    if k > 1 and renormalize:
        # classic top-k MoE renormalizes over the chosen experts; k=1
        # keeps the RAW softmax prob — Switch routing scales by it so the
        # router keeps a gradient (p/p == 1 would zero d/d(gate))
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
    expert = topk_e.reshape(-1)  # (N*k,) routing entries
    gate_p = topk_p.reshape(-1)
    entry_tok = jnp.repeat(jnp.arange(N), k)  # entry -> source token

    # fixed capacity per expert (static shape); position of each entry in
    # its expert's send buffer via a cumulative count
    cap = max(1, int(capacity_factor * N * k / E))
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # (N*k, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based slot per entry
    slot = jnp.sum(pos, axis=1) - 1  # (N*k,) 0-based; -1 if unrouted
    keep = (slot >= 0) & (slot < cap)

    # --- dispatch: (E, cap, D) send buffer, scattered by (expert, slot) --
    disp = jnp.zeros((E, cap, D), x.dtype)
    disp = disp.at[expert, jnp.clip(slot, 0, cap - 1)].add(
        flat[entry_tok] * keep[:, None].astype(x.dtype)
    )

    if ep_axis is not None:
        # tokens travel to their expert's chip: rank r keeps the chunks
        # for its local experts from EVERY rank — the all-to-all
        # transpose (ref all_to_all, c:2123-2218), one XLA all-to-all on
        # ICI (the same lowering as ops.collectives.alltoall).
        recv = lax.all_to_all(
            disp.reshape(ep, e_local, cap, D),
            ep_axis,
            split_axis=0,
            concat_axis=0,
        )  # (src_rank, local_expert, slot, D)
        work = recv.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, D)
    else:
        work = disp  # (E, cap, D)

    # --- expert FFN on the local experts (batched einsum -> MXU) ---------
    up = lambda w: jnp.einsum("ecd,edf->ecf", work, w)
    h = _expert_act(up(params["w1"]), params, up)
    out = jnp.einsum("ecf,efd->ecd", h, params["w2"])

    if ep_axis is not None:
        # inverse all-to-all: results return to each token's home rank
        back_in = out.reshape(e_local, ep, cap, D).transpose(1, 0, 2, 3)
        back = lax.all_to_all(
            back_in, ep_axis, split_axis=0, concat_axis=0
        )  # (expert_owner_rank, local_expert, slot, D)
        combined = back.reshape(E, cap, D)
    else:
        combined = out

    # --- combine: gather each entry's expert output, weight by gate, and
    # sum a token's k contributions ---------------------------------------
    got = combined[expert, jnp.clip(slot, 0, cap - 1)]  # (N*k, D)
    weighted = got * (gate_p * keep.astype(x.dtype))[:, None]
    y = weighted.reshape(N, k, D).sum(axis=1)
    y = y.reshape(B, T, D)
    if tp_axis is not None:
        # w2's input dim was tp-sharded: the combined outputs are
        # partial sums — one allreduce on the (B, T, D) result (smaller
        # than the per-expert buffers) completes the row-parallel form
        y = lax.psum(y, tp_axis)
    if not return_aux:
        return y
    # Switch load-balance: E * sum_e (dispatch fraction)_e * (mean router
    # prob)_e — differentiable through P (f's argmax is a constant), so
    # its gradient pushes probability mass toward under-used experts
    f = onehot.astype(jnp.float32).mean(axis=0)  # (E,) entry fraction
    P = probs.astype(jnp.float32).mean(axis=0)
    load_balance = jnp.asarray(E, jnp.float32) * jnp.sum(f * P)
    router_z = jnp.mean(
        jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2
    )
    return y, {
        "load_balance": load_balance,
        "router_z": router_z,
        "expert_tokens": onehot.sum(axis=0),
        "dropped": jnp.sum(~keep).astype(jnp.int32),
    }
