"""Traffic driver ``train_steps_sdar``: the closed loop of ``train_steps``
(steps back to back, one queued ahead, the window ends on the last loss)
over SDAR-30B-A3B-Chat's block of ``accl_tpu.models`` under its
block-diffusion TRAINING objective, as ONE chip of an 8-way
expert-parallel group computes it: a Qwen3-MoE block (RMSNorm 1e-6, GQA 8
to 1, QK-norm a head, RoPE, a float32 softmax router with top 8 of 128
renormalised, 16 experts held, no shared expert, untied head), the ids
noised inside the step from a fresh key a step, ``[noisy ; clean]`` under
the block layout the flash kernels visit tile by tile, the head and the
``1 / t``-weighted loss on the noisy half only; through
``make_sharded_train_step`` on a world of one chip.

Set-up builds the program's config FIRST, so a tree whose
``TransformerConfig`` lacks the objective fails at once.  The weights are
the seed's; the router matrices are then brought to balance by a fixed
number of rounds of gradient descent on the model's own auxiliary loss
(``balanced``).  Then the check, on the first batch under the first
step's noise (``accl_tpu.models.diffusion_noise`` with the key the step
gets), against the plain float32 reference in
``perfbench/reference/sdar_moe.py`` (given the same noisy ids, levels and
held range; a layer at a time):

* (a) the router's counters through ``make_sharded_router_probe`` on
  ``[noisy ; clean]``: tokens an expert a layer over all 128 and the
  entries held here against the reference's, both within the count of
  near-tie rows; nothing dropped;
* (b) logits of the first sequence's NOISY half through
  ``make_sharded_forward`` at its first and its last ``check_positions``
  positions (early: a query of block 0 sees 4 keys, so one leaked block
  shows; late: the long reductions), on the rows without a near-tie in
  any layer;
* (c) the loss the FIRST train step returns (weighted NLL plus the
  weighted auxiliary loss, through ``make_sharded_train_step`` itself)
  against the reference's of the batch, and its count of masked positions
  against the noise's.

``train_tokens_per_s`` counts DATA tokens (``batch * seq`` a step), not
the ``2 * batch * seq`` rows that go through every layer.
"""

from __future__ import annotations

import math

import numpy as np

from perfbench.drivers import train_steps_trinity
from perfbench.drivers.train_steps_olmoe import router_facts
from perfbench.drivers.train_steps_trinity import held_entries
from perfbench.reference import sdar_moe as reference

#: Limits of the check: the program (bf16 weights and activations, f32
#: accumulation, f32 router softmax) against the float32 reference at
#: "highest" matmul precision, in the form ``train_steps_trinity`` has.
#: Measured on the v5e at the published widths and six layers (my chip
#: runs, PR 38: 39 runs at 39 seeds, after set-up's balance rounds).
#:
#: ROUTING NEAR-TIES as there: a row is NEAR A TIE in a layer where its 8th
#: and 9th router logits are within NEAR_TIE_SPACINGS bf16 spacings (2^-8)
#: of the layer's logit RMS: 2,200-4,700 of a layer's 16,384 rows.  (a)
#: Half the L1 distance of the two tokens-an-expert histograms over all 128
#: must stay under that layer's count of near-tie rows: read 174-596
#: entries a layer against 2,214-4,656; and so must the difference of the
#: entries HELD here (read 0-72); no entry dropped.  (b) Logits of the
#: first sequence's noisy half at its first and last 512 positions, on the
#: rows CLEAN_SPACINGS clear of a tie in EVERY layer.  One expert swapped
#: at a row moves its logits by 0.11-0.39 (RMS 0.90) where bf16's rounding
#: moves them by 0.025-0.03, and the hidden state's error reaches a router
#: logit with about a spacing's size: at 2 spacings (257-331 rows) a
#: compared row had swapped one in 31 of 32 runs (largest error 0.11-0.39,
#: relative RMS 0.0056-0.0116), at 4 spacings (123-150 rows) in 2 of 15
#: (0.15 and 0.18; else 0.026-0.053; relative RMS 0.0053-0.0089), at 8
#: (24-42 rows, too few) in none.  So 4.  (c) The first step's loss
#: (weighted NLL + the weighted auxiliary term) against the reference's:
#: 3.7e-6 to 1.8e-4 apart; and its ``masked_tokens`` against the noise's of
#: the same key: equal.
#:
#: The controls, each past a limit (my chip runs, PR 38, seed 3800002003;
#: ``.probe/controls.py``, not committed; at this margin, 107-118 rows).
#: The nearest precision below bf16, the reference from weights rounded to
#: fp8 (e5m2, ``lax.reduce_precision``): relative RMS 0.236, largest error
#: 1.12, loss 1.0e-3 apart, 13,794-22,745 entries a layer moved against
#: 2,175-4,118 allowed: past every limit, by 7.9, 1.9, 2.2 and 3.9-10
#: times.  The reference with ``col // 4 <= row // 4`` in the noisy ->
#: clean part (a noisy query sees its OWN clean block, the ids it is to
#: predict): relative RMS 0.066 (0.088 over the early rows, 0.0058 over the
#: late ones, where four leaked keys among thousands move nothing),
#: largest error 1.34; loss 2.4e-4, inside.  The reference with the loss
#: shifted by one position: loss 3.4e-3 apart, logits untouched.  Each
#: limit lies between its two readings: 3.4, 3.4 and 2.4 times the largest
#: reading of the runs and 2.2, 1.9 and 2.2 times under the nearest
#: control.  So does each way of breaking the model that
#: ``tests/test_sdar.py`` lists.
REL_RMS_LIMIT = 0.03
MAX_ABS_LIMIT = 0.6
LOSS_REL_LIMIT = 4.5e-4
NEAR_TIE_SPACINGS = 2.0
#: the margin a checked row must be clear of a tie by, in every layer, for
#: its logits to be compared
CLEAN_SPACINGS = 4.0
#: what a run prints beside the one it is judged by, for the next look
_NEAR_TIE_LOOK = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
#: fewer rows without a near-tie than this: nothing to compare on
MIN_CLEAN_POSITIONS = 32

#: The rate of each round of gradient descent on the model's auxiliary
#: loss (the Switch load-balance term, averaged over the layers) that
#: set-up runs on the router matrices before anything is checked or timed
#: (the configuration file's ``departures`` says why): a round is one step
#: on each of the cell's token batches in turn, each under a noise key of
#: its own; the gates are held in float32 through the rounds and rounded
#: to the weights' type once, after the last.  2.3 s a round on the v5e.
#: Over six seeds x four batches (my chip runs, PR 38) the held share of
#: a batch's entries reads 12.27-12.80% a seed after these four at 0.1 (a
#: layer's 9.9-14.2%, the fullest expert 3,400-4,400 entries, the mask
#: rows' 4,096 among them), 11.15-13.29% at the seeded routers (a layer's
#: 8.3-17.1%, the fullest 7,600-9,900) and 10.07-13.06% after eight at 0.4,
#: which overshoot; nothing dropped in any of the three.
BALANCE_RATES = (0.1,) * 4

#: noise keys made in set-up, one a step: more than any window can take
STEP_KEYS = 1024


def program_config(config: dict):
    """The published keys as ``accl_tpu.models.TransformerConfig``."""
    import jax.numpy as jnp

    from accl_tpu.models import BlockDiffusion, TransformerConfig

    program, assumed = config["program"], config["assumed"]
    if config["model_type"] != "sdar_moe" or config["hidden_act"] != "silu":
        raise ValueError("the block is sdar_moe's, gated SiLU")
    if (
        config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]
        or config["attention_bias"] or config["use_sliding_window"]
        or config["rope_scaling"] is not None
    ):
        raise ValueError(
            "every layer is sparse and full attention; no bias, no rope "
            "scaling"
        )
    held, of = config["num_experts"], config["num_router_experts"]
    return TransformerConfig(
        vocab=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"],
        max_seq=config["max_position_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            config["torch_dtype"]
        ],
        pos_embedding="rope",
        rope_base=float(config["rope_theta"]),
        norm="rmsnorm",
        norm_eps=float(config["rms_norm_eps"]),
        ffn="swiglu",
        qk_norm="head",
        tie_head=config["tie_word_embeddings"],
        diffusion=BlockDiffusion(
            block=int(assumed["block_length"]),
            mask_id=int(config["mask_token_row"]),
            eps=float(assumed["noise_eps"]),
        ),
        n_experts=held,
        moe_top_k=config["num_experts_per_tok"],
        moe_capacity_factor=None,
        moe_norm_topk_prob=config["norm_topk_prob"],
        moe_aux_weight=float(assumed["router_aux_loss_coef"]),
        moe_router_z_weight=0.0,
        moe_router_experts=None if of == held else of,
        moe_first_expert=config["first_expert"],
        moe_held_row_factor=float(program["held_row_factor"]),
        attention=program["attention"],
        remat=program["remat"],
    )


def reference_model(config: dict) -> dict:
    """The keyword arguments ``reference.layer`` takes, from the keys."""
    return dict(
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        block=int(config["assumed"]["block_length"]),
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        first_expert=config["first_expert"],
    )


def reference_weights(params: dict) -> dict:
    """The program's parameter tree under Qwen3-MoE's names (the held
    experts' matrices stacked on a leading axis)."""

    def layer(lp):
        moe = lp["moe"]
        return {
            "input_layernorm": lp["ln1"],
            "q_proj": lp["wq"], "k_proj": lp["wk"], "v_proj": lp["wv"],
            "o_proj": lp["wo"],
            "q_norm": lp["q_norm"], "k_norm": lp["k_norm"],
            "post_attention_layernorm": lp["ln2"],
            "router": moe["gate"],
            "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
            "experts.down_proj": moe["w2"],
        }

    return {
        "embed_tokens": params["embed"],
        "norm": params["ln_f"],
        "lm_head": params["head"],
        "layers": [layer(lp) for lp in params["layers"]],
    }


def _with_gates(params: dict, gates) -> dict:
    """``params`` with the layers' router matrices replaced."""
    return {**params, "layers": [
        {**lp, "moe": {**lp["moe"], "gate": g}}
        for lp, g in zip(params["layers"], gates)
    ]}


def balanced(params, batches, keys, cfg, rates=BALANCE_RATES):
    """``params`` with every layer's router matrix moved by ``len(rates)``
    rounds of gradient descent on the model's own auxiliary loss as the
    loss weighs it (the program's ``loss_fn`` computes it, on ``[noisy ;
    clean]`` under ``keys``' noise), nothing else in the objective and no
    other parameter moved: one step on each of ``batches`` a round, the
    gates in float32 through the rounds.  The rate is of the UNWEIGHTED
    term (the weight, 0.001, would ask for rates in the hundreds)."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.models.transformer import loss_fn

    def penalty(gates, params, tokens, key):
        tree = _with_gates(params, [g.astype(cfg.dtype) for g in gates])
        _, aux = loss_fn(tree, tokens, key, cfg, with_aux=True)
        return aux["load_balance"] / cfg.n_layers

    @jax.jit
    def round_(gates, params, tokens, key, rate):
        grads = jax.grad(penalty)(gates, params, tokens, key)
        return [g - rate * d for g, d in zip(gates, grads)]

    gates = [lp["moe"]["gate"].astype(jnp.float32) for lp in params["layers"]]
    for rate in rates:
        for tokens, key in zip(batches, keys):
            gates = round_(gates, params, tokens, key, rate)
    return _with_gates(params, [
        jax.device_put(g.astype(lp["moe"]["gate"].dtype),
                       lp["moe"]["gate"].sharding)
        for g, lp in zip(gates, params["layers"])
    ])


class _KeyedStep:
    """The compiled step as ``train_steps``' loop calls it, ``(params,
    tokens, targets) -> (params, loss)``: the third argument is ignored
    and a FRESH noise key a step is handed to the program in its place;
    the step's ``masked_tokens`` counters are kept (device scalars, summed
    after the window)."""

    def __init__(self, compiled, keys):
        self.compiled, self.keys = compiled, keys
        self.calls, self.masked = 0, []

    def __call__(self, params, tokens, _targets):
        key = self.keys[self.calls]
        self.calls += 1
        params, loss, counters = self.compiled(params, tokens, key)
        self.masked.append(counters["masked_tokens"])
        return params, loss

    def as_text(self) -> str:
        return self.compiled.as_text()


class Driver(train_steps_trinity.Driver):
    """``_segment`` and ``_note_loss`` are ``train_steps``'; ``measure``
    is ``train_steps_trinity``'s with the noise's counters beside it."""

    def setup(self) -> None:
        cfg = program_config(self.config)   # first: see the module docstring

        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from accl_tpu.models import (
            diffusion_noise,
            init_params,
            make_sharded_forward,
            make_sharded_router_probe,
            make_sharded_train_step,
        )
        from accl_tpu.models.transformer import (
            normalize_spec,
            param_specs,
            resolve_attention,
        )
        from accl_tpu.ops.pallas.attention import flash_tile_classes

        self._mark("imports")
        tr = self.traffic
        B, L = int(tr["batch"]), int(tr["seq"])
        if L > cfg.max_seq:
            raise ValueError(
                f"seq {L} past max_position_embeddings {cfg.max_seq}"
            )
        # T: the DATA tokens of a sequence, what tokens/s counts
        self.B, self.T = B, L
        block = cfg.diffusion.block
        mesh = Mesh(np.array([self.device]).reshape(1, 1), ("dp", "tp"))

        q = jax.ShapeDtypeStruct(
            (B, cfg.n_heads, 2 * L, cfg.head_size()), jnp.dtype(cfg.dtype)
        )
        self.attention = resolve_attention(cfg.attention, q)
        if not self.rehearse and self.attention != "flash":
            self.problems.append(
                f"attention={cfg.attention!r} resolved to "
                f"{self.attention!r}, not 'flash'"
            )
        # from the shapes, by the kernels' own ranges
        self.tiles = flash_tile_classes(
            2 * L, dtype=cfg.dtype, block_diffusion=(L, block)
        )

        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, normalize_spec(s)),
            param_specs(cfg),
            is_leaf=lambda x: isinstance(x, P),
        )
        replicated = NamedSharding(mesh, P())
        key = jax.device_put(jax.random.PRNGKey(self.seed), replicated)
        scale = float(self.config["program"]["embed_init_scale"])

        def make_params(k):
            # the configuration file's ``departures`` says why the table
            # is scaled
            params = init_params(k, cfg)
            return {**params, "embed": params["embed"] * scale}

        params = jax.jit(make_params, out_shardings=shardings)(key)

        n = int(tr["token_batches"])
        mask_id = cfg.diffusion.mask_id
        if mask_id != cfg.vocab - 1:
            raise ValueError("the mask id is the slice's last row")

        def make_tokens(k):
            # data ids from the held slice of the vocabulary bar the mask
            # id's row; a noise key a step
            tok = jax.random.randint(
                jax.random.fold_in(k, 1), (n, B, L), 0, mask_id, jnp.int32
            )
            return tok, jax.random.split(jax.random.fold_in(k, 2), STEP_KEYS)

        tok, keys = jax.jit(
            make_tokens, out_shardings=(replicated, replicated)
        )(key)
        self.tokens = list(tok)
        self.targets = [None] * n     # the loop's third argument: unused
        keys = list(keys)
        jax.block_until_ready((params, tok, keys))
        self._mark("weights_and_tokens")

        params = balanced(params, self.tokens, keys[-n:], cfg)
        jax.block_until_ready(params)
        self._mark("router_balanced")
        fwd, _ = make_sharded_forward(cfg, mesh)
        probe = make_sharded_router_probe(cfg, mesh)
        noise = jax.jit(lambda k, t: diffusion_noise(k, t, cfg.diffusion))
        want_loss, want_masked = self._check(
            fwd, probe, params, cfg, noise(keys[0], self.tokens[0])
        )
        self._mark("reference_check")

        # compiled ONCE, ahead of time: nothing can compile in the window
        step, _ = make_sharded_train_step(cfg, mesh, lr=float(tr["lr"]))
        compiled = step.lower(params, self.tokens[0], keys[0]).compile()
        self.step = _KeyedStep(compiled, keys)
        mem = compiled.memory_analysis()
        live = (self.device.memory_stats() or {}).get("bytes_in_use", 0)
        # as train_steps: the arrays alive at a step's start, its outputs
        # that alias no argument, and its scratch
        self.step_peak_bytes = int(
            live + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes
        ) if mem is not None else 0
        self.step_memory = None if mem is None else {
            "live_bytes": int(live),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        self.params = params
        for i in range(2):
            self.params, loss = self.step(
                self.params, self.tokens[i % n], None
            )
            if i == 0:
                self._check_loss(
                    float(loss), want_loss, int(self.step.masked[0]),
                    want_masked,
                )
        self._note_loss(float(loss))
        self._mark("step_warm_up")

    # -- the check -----------------------------------------------------------

    def _reference(self, params, noisy, clean, masked, t, rows):
        """The reference on one batch, a layer at a time: logits of the
        first sequence's noisy half at ``rows``, the loss, and each
        layer's router logits."""
        import jax
        import jax.numpy as jnp

        model = reference_model(self.config)
        weights = reference_weights(params)

        @jax.jit
        def one_layer(h, lp):
            with jax.default_matmul_precision("highest"):
                return reference.layer(
                    h, lp, q_block=min(512, 2 * self.T), **model
                )

        @jax.jit
        def finish(h, top, balance):
            with jax.default_matmul_precision("highest"):
                z = reference.head(top, reference.noisy_half(h))
                loss = reference.weighted_nll(z, clean, masked, t)
            return z[0][rows], loss + reference.AUX_COEF * balance

        h = jax.jit(reference.embed)(
            weights, jnp.concatenate([noisy, clean], axis=1)
        )
        logits, balance = [], 0.0
        for lp in weights["layers"]:
            h, logits_l, balance_l = one_layer(h, lp)
            logits.append(logits_l)
            balance = balance + balance_l / len(weights["layers"])
        top = {k: v for k, v in weights.items() if k != "layers"}
        want, loss = finish(h, top, balance)
        return want, float(loss), logits

    def _check(self, fwd, probe, params, cfg, noise) -> tuple:
        """Logits and router counters of the first batch under the first
        step's noise against the reference; returns the reference's loss
        of that batch and the noise's count of masked positions."""
        import jax
        import jax.numpy as jnp

        L = self.T
        span = min(int(self.traffic["check_positions"]), L // 2)
        rows = np.concatenate([np.arange(span), np.arange(L - span, L)])
        noisy, masked, t = noise
        clean = self.tokens[0]
        both = jnp.concatenate([noisy, clean], axis=1)
        got = jax.jit(lambda z: z[0][rows].astype(jnp.float32))(
            fwd(params, both)
        )
        counters = probe(params, both)
        first, held = cfg.moe_first_expert, cfg.n_experts
        want, want_loss, logits = self._reference(
            params, noisy, clean, masked, t, rows
        )
        facts = [jax.jit(router_facts, static_argnums=1)(z, cfg.moe_top_k)
                 for z in logits]
        want_counts = np.stack([np.asarray(f[0]) for f in facts])
        gaps = np.stack([np.asarray(f[1]) for f in facts])      # (layers, N)
        near_tie = gaps < NEAR_TIE_SPACINGS
        allowed = near_tie.sum(axis=1)
        near = np.stack([(gaps < m).sum(axis=1) for m in _NEAR_TIE_LOOK])
        # the first sequence's noisy half is rows 0..L of the flattened
        clean_rows = ~(gaps < CLEAN_SPACINGS).any(axis=0)[rows]

        def compare(got, want, keep):
            err = (got - want) * keep[:, None]
            ref = want * keep[:, None]
            return (
                jnp.sqrt(jnp.sum(err ** 2) / jnp.sum(ref ** 2)),
                jnp.max(jnp.abs(err)),
                jnp.sqrt(jnp.sum(ref ** 2) / (keep.sum() * want.shape[1])),
            )

        compare = jax.jit(compare)
        keep = clean_rows.astype(np.float32)
        early = np.arange(2 * span) < span
        rel_rms, max_abs, ref_rms = (float(x) for x in compare(got, want, keep))
        parts = {
            name: [float(x) for x in compare(got, want, keep * part)[:2]]
            for name, part in (("early", early), ("late", ~early))
        }
        rel_rms_all, max_abs_all, _ = (
            float(x) for x in compare(got, want, np.ones_like(keep))
        )
        # the same two readings over the rows clear by other margins
        look = {}
        for m in _NEAR_TIE_LOOK:
            rows_m = (~(gaps < m).any(axis=0)[rows]).astype(np.float32)
            if rows_m.sum():
                look[str(m)] = [int(rows_m.sum())] + [
                    float(x) for x in compare(got, want, rows_m)[:2]
                ]
        counts = np.asarray(counters["expert_tokens"])
        dropped = int(np.asarray(counters["dropped"]).sum())
        here = np.asarray(counters["held_entries"])
        want_here = held_entries(want_counts, first, held)
        # an entry that went to another expert than the reference's moves
        # one count down and one up: half the L1 distance of the two
        # histograms is a lower bound on such entries, a layer
        moved = np.abs(counts - want_counts).sum(axis=1) // 2
        held_off = np.abs(here - want_here)
        n_clean = int(clean_rows.sum())
        entries = counts.sum(axis=1)
        n_masked = int(np.asarray(masked).sum())
        self.attempted += 1
        self.check = {
            "positions": int(rows.size), "clean_positions": n_clean,
            "rel_rms": rel_rms, "max_abs": max_abs,
            "early_late": parts,
            "rel_rms_all": rel_rms_all, "max_abs_all": max_abs_all,
            "clean_look": look,
            "reference_rms": ref_rms, "attention": self.attention,
            "dropped": dropped,
            "moved_entries": moved.tolist(),
            "held_entries": here.tolist(),
            "reference_held_entries": want_here.tolist(),
            "allowed_entries": allowed.tolist(),
            "masked_positions": n_masked,
            "near_ties": {
                str(m): near[i].tolist() for i, m in enumerate(_NEAR_TIE_LOOK)
            },
        }
        held_counts = counts[:, first:first + held]
        self.router = {
            "expert_tokens": counts.tolist(),
            # over the HELD experts: the load this chip's bank sees
            "load_imbalance": float(
                (held_counts.max(axis=1)
                 / np.maximum(held_counts.mean(axis=1), 1e-9)).max()
            ),
            "held_entries": here.tolist(),
            "entries": entries.tolist(),
            "held_entry_share": float(100.0 * here.sum() / entries.sum()),
        }
        bad = []
        if n_clean < MIN_CLEAN_POSITIONS:
            bad.append(
                f"only {n_clean} of the {rows.size} checked rows have no "
                f"router near-tie in any layer: too few to compare logits on"
            )
        elif not (rel_rms <= REL_RMS_LIMIT and max_abs <= MAX_ABS_LIMIT):
            bad.append(
                f"logits differ from the reference: rel rms {rel_rms:.4g} "
                f"(limit {REL_RMS_LIMIT}), max abs {max_abs:.4g} "
                f"(limit {MAX_ABS_LIMIT}) over {n_clean} rows"
            )
        if dropped:
            bad.append(
                f"{dropped} held routing entries past the row buffer "
                f"(held {here.tolist()})"
            )
        if (moved > allowed).any():
            bad.append(
                f"tokens an expert: {moved.tolist()} entries a layer moved "
                f"against the reference's top-k, more than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if (held_off > allowed).any():
            bad.append(
                f"entries held here {here.tolist()} against the reference's "
                f"{want_here.tolist()}: further apart than its near-ties "
                f"allow ({allowed.tolist()})"
            )
        if bad:
            self.failed += 1
            self.problems.extend(bad)
        return want_loss, n_masked

    def _check_loss(self, got: float, want: float, masked: int,
                    want_masked: int) -> None:
        self.attempted += 1
        rel = abs(got - want) / abs(want)
        self.check.update(
            loss=got, reference_loss=want, loss_rel=rel, step_masked=masked
        )
        if not (math.isfinite(got) and rel <= LOSS_REL_LIMIT):
            self.failed += 1
            self.problems.append(
                f"first step's loss {got:.6g} against the reference's "
                f"{want:.6g}: {rel:.3g} apart (limit {LOSS_REL_LIMIT})"
            )
        if masked != want_masked:
            self.failed += 1
            self.problems.append(
                f"the first step masked {masked} positions, the noise of "
                f"its key {want_masked}"
            )

    def measure(self, seconds: float, tracer) -> dict:
        result = super().measure(seconds, tracer)
        import jax

        result["facts"]["diffusion"] = {
            "masked_tokens": int(np.sum(jax.device_get(self.step.masked))),
            "noisy_positions": self.step.calls * self.B * self.T,
            "block": int(self.config["assumed"]["block_length"]),
        }
        result["facts"]["attention_tiles"] = self.tiles
        return result
