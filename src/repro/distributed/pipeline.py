"""GPipe-style pipeline parallelism over a mesh axis (default: "pod").

At two-pod scale the ``pod`` axis crosses DCN, where all-reducing every
gradient (outer data parallelism) costs a full model round-trip per step.
Pipelining over pods changes the cross-pod wire cost to ONE activation
hand-off per microbatch per boundary — for kimi-k2-class models that is
~2000x fewer DCN bytes than gradient mirroring (activations [mb,S,D]
vs 1T gradients), the textbook reason trillion-parameter fleets pipeline
across their slowest interconnect.

Mechanics (``jax.shard_map`` manual over the stage axis, auto over
data/model — GSPMD keeps doing TP/FSDP *inside* each stage):

* the stacked layer-group params ``blocks`` [G, ...] are sharded over the
  stage axis (G/S groups per stage) — that IS the pipeline placement;
* the batch is split into M microbatches; a ``lax.scan`` runs
  T = M + S - 1 ticks; each tick applies this stage's layer groups to its
  current activation and ``ppermute``s the result to the next stage;
* stage 0 injects microbatch t on tick t (t < M); the last stage's
  outputs for ticks >= S-1 are the pipeline's outputs, gathered with a
  one-hot mask + psum over the stage axis (bubble fraction
  (S-1)/(M+S-1), the GPipe schedule);
* ``jax.grad`` differentiates straight through: the AD transpose of
  ``ppermute`` is the reverse permute, so the backward pipeline runs
  automatically in the opposite direction.

Embedding / final-norm / unembed run replicated across stages outside the
shard_map (negligible compute; GSPMD dedups).  Scope: the decoder-only
("dense"/"moe"-family) stack with TP/ZeRO-1 storage — heterogeneous
stacks (zamba2's shared block, whisper's encoder) and FSDP-stored archs
keep the pod axis as data parallelism (GSPMD's partial-manual mode
re-replicates FSDP-sharded operand dims entering the shard_map, which
defeats FSDP; a known sharp edge of mixing manual stage placement with
auto parameter sharding).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.layers import apply_norm
from repro.models.transformer import _apply_block, _positions_embed, program_for

__all__ = ["make_pp_forward", "pp_lm_loss"]


def _split_microbatches(x, n):
    B = x.shape[0]
    assert B % n == 0, (B, n)
    return x.reshape(n, B // n, *x.shape[1:])


def make_pp_forward(cfg: ModelConfig, mesh, n_microbatches: int,
                    stage_axis: str = "pod"):
    """Returns forward(params, tokens) -> final hidden states [B, S, D],
    pipelined over ``stage_axis``.  Requires a homogeneous decoder stack
    (program remainder empty) whose group count divides the stage count.
    """
    grp, n_groups, rem = program_for(cfg)
    assert not rem, "PP needs a homogeneous stack (no remainder groups)"
    S = mesh.shape[stage_axis]
    assert n_groups % S == 0, (n_groups, S)
    M = n_microbatches

    def stage_body(blocks_local, x_mb):
        """Run this stage's layer groups on one microbatch activation."""
        def group_body(carry, gp):
            x, aux = carry
            for i, kind in enumerate(grp):
                p = gp[f"b{i}_{kind}"]
                x, aux = _apply_block(cfg, kind, p, x, None, aux, None)
            return (x, aux), None
        if cfg.remat != "none":
            group_body = jax.checkpoint(group_body)
        (x, aux), _ = jax.lax.scan(group_body, (x_mb, jnp.float32(0.0)),
                                   blocks_local)
        return x, aux

    def pipelined(blocks_local, xs_mb):
        """shard_map body: manual over stage_axis.

        blocks_local: this stage's [G/S, ...] params.
        xs_mb: [M, mb, S, D] embedded microbatches (same on every stage).
        Returns (y [T-S+1, mb, S, D] last-stage outputs, aux [1]).

        NOTE the feed enters every stage replicated: shard_map realizes
        the unvarying->varying conversion as a psum_invariant (an
        all-inputs-identical exchange).  Kept in f32 because XLA-CPU's
        bf16 AllReducePromotion pass crashes cloning copy-reducers; the
        roofline charges it as real traffic (conservative - on TPU it is
        a no-op copy).  Feeding s32 tokens and embedding inside stage 0
        would shrink it D-fold but trips an SPMD partition-grouping CHECK
        in this XLA version - revisit on a newer toolchain.
        """
        sid = jax.lax.axis_index(stage_axis)
        T = M + S - 1
        mb_shape = xs_mb.shape[1:]

        def tick(carry, t):
            inp, aux_acc = carry
            # stage 0 ingests microbatch t (zeros once the feed drains)
            feed = jnp.where(t < M, xs_mb[jnp.minimum(t, M - 1)],
                             jnp.zeros(mb_shape, xs_mb.dtype))
            x = jnp.where(sid == 0, feed.astype(jnp.float32),
                          inp.astype(jnp.float32)).astype(xs_mb.dtype)
            y, aux = stage_body(blocks_local, x)
            # hand to the next stage (last stage's send is dropped by
            # the ring edge going back to 0, which stage 0 ignores)
            nxt = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            # last stage emits microbatch t-(S-1) on tick t (y*0 keeps the
            # masked branch varying - no bf16 psum_invariant)
            emit = jnp.where(sid == S - 1, y, y * 0)
            return (nxt, aux_acc + aux), emit

        # the carry becomes stage-varying after one tick; start it varying
        # via sid arithmetic (jax.lax.pcast would also work, but its
        # copy-reducer all-reduce trips XLA-CPU's AllReducePromotion pass
        # at 512 devices)
        zero_var = (sid * 0).astype(xs_mb.dtype)
        init = (jnp.zeros(mb_shape, xs_mb.dtype) + zero_var,
                jnp.float32(0.0) + zero_var.astype(jnp.float32))
        (_, aux_total), emits = jax.lax.scan(tick, init, jnp.arange(T))
        # emits [T, mb, S, D]: valid rows are ticks S-1..T-1 on the LAST
        # stage (zeros elsewhere).  Returned stage-stacked via out_specs
        # (the caller slices the last stage's block) — an explicit psum
        # here trips XLA-CPU's AllReducePromotion pass on this shape.
        y = emits[S - 1:]
        return y, (aux_total / (M * n_groups))[None]

    def forward(params, tokens):
        x = _positions_embed(cfg, params, tokens)
        xs = _split_microbatches(x, M)
        y, aux = jax.shard_map(
            pipelined, mesh=mesh,
            in_specs=(P(stage_axis), P()),
            out_specs=(P(stage_axis), P(stage_axis)),
            axis_names={stage_axis},
        )(params["blocks"], xs)
        y = y[-M:]                                       # last stage's block
        aux = jnp.sum(aux)                               # sum over stages
        y = y.reshape(-1, *y.shape[2:])                  # [B, S, D]
        y = apply_norm(params["final_norm"], y, cfg.norm_eps, cfg.norm,
                       cfg.norm_mult_dtype == "float32",
                       custom_bwd=bool(cfg.norm_custom_bwd))
        return y, aux

    return forward


def pp_lm_loss(params: dict, cfg: ModelConfig, batch: dict, forward) -> jax.Array:
    """Next-token loss on the pipelined forward (mirrors lm_loss)."""
    y, aux = forward(params, batch["tokens"])
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", y, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", y, params["unembed"])
    with jax.named_scope("f32c"):
        logits = logits.astype(jnp.float32)[:, :-1]
        targets = batch["tokens"][:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(targets, cfg.vocab_size, dtype=jnp.float32)
        nll = jnp.mean(lse - jnp.sum(logits * onehot, axis=-1))
    return nll + 0.01 * aux
