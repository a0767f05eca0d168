"""Transformer language model -- the zoo's long-context family.

BEYOND-REFERENCE: the reference zoo (ref: scripts/tf_cnn_benchmarks/
models/model_config.py:38-142) has no transformer/LM family; this model
makes the framework's long-context machinery reachable through the
stock CLI like any other zoo member:

    python -m kf_benchmarks_tpu.cli --model=transformer_lm \
        --batch_size=8 --use_fp16=true

A GPT-style decoder-only LM (pre-LN blocks, learned positions) whose
attention core is ``parallel/sequence.blockwise_attention`` -- the
flash-style online-softmax schedule measured in PERF.md (exact causal
attention at 64k tokens on one 16 GB chip, 2-4x faster than
materialised-score attention at every length). Synthetic data follows
the NCF/DeepSpeech pattern: int32 token ids ride the feature slot,
next-token ids the label slot; throughput prints as sequences/sec on
the standard step line (x seq_len for tokens/sec).

HBM footprint (the round-7 pass; PERF.md):

* The L identical blocks run as ONE scanned layer (nn.scan) with
  ``jax.checkpoint`` per block (nn.remat), so the compiled program
  carries one block body instead of L copies and the backward pass
  keeps one block-boundary residual per layer instead of every
  intermediate.
* The LM head never materializes the (B, T, V) logits tensor: the
  module returns ``ops.fused_loss.FusedLMHead`` (final hidden states +
  unembedding kernel) and the loss/accuracy functions reduce it chunk
  at a time (peak temp O(B*chunk*V); bit-exact against the monolithic
  head, tests/test_fused_loss.py).

Both levers are env-switchable for on-chip A/Bs:
KF_TRANSFORMER_LM_HEAD in ('fused', 'dense'),
KF_TRANSFORMER_LM_LAYERS in ('scan', 'loop').
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from kf_benchmarks_tpu.models import model as model_lib
from kf_benchmarks_tpu.ops import fused_loss as fused_loss_lib
from kf_benchmarks_tpu.parallel import sequence as sequence_lib

VOCAB = 32768
SEQ_LEN = 2048
D_MODEL = 512
N_LAYERS = 6
N_HEADS = 8
D_FF = 2048
ATTN_BLOCK = 512
# Two-level (q x kv) tiling: accumulators stay q-block-sized instead of
# full-length, and causal runs skip strictly-future K/V blocks.
ATTN_Q_BLOCK = 512


class _Block(nn.Module):
  """One pre-LN decoder block; the unit nn.scan stacks L-fold.

  The (carry, None) -> (carry, None) signature is the nn.scan contract;
  the loop fallback calls it with the same shape so the two layer
  paths share one body (and therefore cannot drift numerically).
  """
  d_model: int
  n_heads: int
  d_ff: int
  attn_block: int
  attn_q_block: int
  attn_impl: str
  dtype: Any
  param_dtype: Any
  # Serving (kf_benchmarks_tpu/serving/): decode=True switches the
  # block to the single-token KV-ring path -- carry (x (B,1,D), pos
  # (B,)), scanned input/output = this layer's (k, v) ring buffers.
  # return_kv=True makes the TRAINING/forward branch also emit its
  # per-position K/V projections as scan outputs (the packed-prefill
  # cache source). Both default off, so the training program -- and
  # every golden contract -- is untouched. decode_exact routes the
  # decode attention through the full-sequence op graph (the
  # bit-identity oracle mode; sequence.decode_attention).
  decode: bool = False
  return_kv: bool = False
  decode_exact: bool = False
  # Paged KV cache (serving/decode.py paged mode): >0 switches the
  # decode branch's per-layer cache from a (B, T, H, Dh) ring slab to a
  # shared (P, page, H, Dh) page POOL -- the carry additionally rides
  # the (B, pages_per_slot) page table, writes scatter into the pool
  # row the table maps pos's page to, and attention gathers pages
  # (sequence.decode_attention page_table mode). 0 = the dense ring
  # (every existing program unchanged).
  kv_page_size: int = 0

  @nn.compact
  def __call__(self, carry, xs):
    dense = lambda feats, name, bias=True: nn.Dense(
        feats, use_bias=bias, name=name, dtype=self.dtype,
        param_dtype=self.param_dtype)
    # LayerNorm computes in f32 (bf16 mean/variance loses too much);
    # the surrounding denses cast back down.
    ln = lambda name: nn.LayerNorm(name=name, dtype=jnp.float32,
                                   param_dtype=self.param_dtype)
    head_dim = self.d_model // self.n_heads
    if self.decode and self.kv_page_size:
      # Paged single-token decode: this layer's cache is the shared
      # (P, page, H, Dh) pool; the slot's page table (carry) maps its
      # logical page for ``pos`` to a pool row. Same submodules as the
      # dense branch; the write is a batched scatter at (table[b,
      # pos//page], pos%page) -- inactive/completed slots carry an
      # all-zero table row, so their writes land on pool row 0, the
      # engine's never-allocated scratch page (serving/engine.py).
      x, pos, table = carry
      ck, cv = xs
      b = x.shape[0]
      page = self.kv_page_size
      t_logical = table.shape[1] * page
      h = ln("ln1")(x).astype(self.dtype)
      qkv = dense(3 * self.d_model, "qkv", bias=False)(h)
      qkv = qkv.reshape(b, 1, 3, self.n_heads, head_dim)
      rpos = pos % t_logical
      pg = jnp.take_along_axis(table, (rpos // page)[:, None],
                               axis=1)[:, 0]                   # (B,)
      ck = ck.at[pg, rpos % page].set(qkv[:, 0, 1])
      cv = cv.at[pg, rpos % page].set(qkv[:, 0, 2])
      att = sequence_lib.decode_attention(
          qkv[:, :, 0], ck, cv, pos, block=page,
          impl=self.attn_impl, exact=self.decode_exact,
          q_block=page, page_table=table)
      x = x + dense(self.d_model, "attn_out")(
          att.reshape(b, 1, self.d_model))
      h = ln("ln2")(x).astype(self.dtype)
      h = nn.gelu(dense(self.d_ff, "mlp_up")(h))
      x = x + dense(self.d_model, "mlp_down")(h)
      return (x, pos, table), (ck, cv)
    if self.decode:
      # Single-token decode over the KV ring buffer. Same submodule
      # names as the forward branch, so trained/initialized variables
      # apply unchanged; op-for-op the forward row's computation, so
      # per-token logits are bit-identical to the full-sequence
      # forward at every prefix length (tests/test_serving.py).
      x, pos = carry
      ck, cv = xs
      b = x.shape[0]
      t_cache = ck.shape[1]
      h = ln("ln1")(x).astype(self.dtype)
      qkv = dense(3 * self.d_model, "qkv", bias=False)(h)
      qkv = qkv.reshape(b, 1, 3, self.n_heads, head_dim)
      # Ring write at pos % T (pure select, no arithmetic on the kept
      # entries -- the bit-identity contract again).
      write = (jnp.arange(t_cache)[None, :] ==
               (pos % t_cache)[:, None])[..., None, None]
      ck = jnp.where(write, qkv[:, :, 1], ck)
      cv = jnp.where(write, qkv[:, :, 2], cv)
      att = sequence_lib.decode_attention(
          qkv[:, :, 0], ck, cv, pos,
          block=min(self.attn_block, t_cache), impl=self.attn_impl,
          exact=self.decode_exact,
          q_block=min(self.attn_q_block, t_cache))
      x = x + dense(self.d_model, "attn_out")(
          att.reshape(b, 1, self.d_model))
      h = ln("ln2")(x).astype(self.dtype)
      h = nn.gelu(dense(self.d_ff, "mlp_up")(h))
      x = x + dense(self.d_model, "mlp_down")(h)
      return (x, pos), (ck, cv)
    # Carry = (hidden states, packed segment ids or None): the segment
    # ids ride the scan carry unchanged so every block's attention sees
    # them without a second scan input (--packed_sequences).
    x, seg = carry
    b, t, _d = x.shape
    h = ln("ln1")(x).astype(self.dtype)
    qkv = dense(3 * self.d_model, "qkv", bias=False)(h)
    qkv = qkv.reshape(b, t, 3, self.n_heads, head_dim)
    blk = min(self.attn_block, t)
    if self.attn_impl == "flash":
      # Matched tilings: the A/B against the tiled path must not
      # confound kernel choice with tile size, so the kernel gets
      # the same block as the scan (long_context_probe.py ditto).
      # Packed runs ride the kernel's native SegmentIds support.
      att = sequence_lib.pallas_flash_attention(
          qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal=True,
          block=blk, segment_ids=seg)
    elif self.attn_impl == "tiled":
      att = sequence_lib.blockwise_attention(
          qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
          block_size=blk, causal=True,
          q_block_size=min(self.attn_q_block, t), segment_ids=seg)
    else:
      raise ValueError(
          f"attn_impl must be 'tiled' or 'flash', got "
          f"{self.attn_impl!r}")
    x = x + dense(self.d_model, "attn_out")(
        att.reshape(b, t, self.d_model))
    h = ln("ln2")(x).astype(self.dtype)
    h = nn.gelu(dense(self.d_ff, "mlp_up")(h))
    x = x + dense(self.d_model, "mlp_down")(h)
    # return_kv: the per-position K/V projections ride the scan outputs
    # (stacked (L, B, T, H, Dh) by nn.scan) -- exactly the arrays a
    # decode step would have written at those positions, so a packed
    # prefill builds the same ring-buffer contents the incremental path
    # would (serving/decode.py). None keeps the legacy program.
    if self.return_kv:
      return (x, seg), (qkv[:, :, 1], qkv[:, :, 2])
    return (x, seg), None


class _TransformerLMModule(nn.Module):
  vocab: int = VOCAB
  d_model: int = D_MODEL
  n_layers: int = N_LAYERS
  n_heads: int = N_HEADS
  d_ff: int = D_FF
  attn_block: int = ATTN_BLOCK
  attn_q_block: int = ATTN_Q_BLOCK
  # 'tiled' (XLA two-level scan) or 'flash' (the TPU Pallas kernel) --
  # switchable per run via KF_TRANSFORMER_LM_ATTN for on-chip A/Bs.
  attn_impl: str = "tiled"
  # True: ONE scanned+rematerialized block (params carry a leading
  # layer axis under 'blocks'); False: the unrolled per-layer loop
  # (params under 'block_{i}') -- the equivalence oracle and the
  # program-size A/B.
  scan_layers: bool = True
  # True: return ops.fused_loss.FusedLMHead (hidden, kernel) so the
  # loss reduces chunk-wise without a (B, T, V) tensor; False:
  # materialize logits (the monolithic head the oracle tests pin
  # against).
  fused_head: bool = True
  # --shard_params (full FSDP): per-block gather hook
  # (ops/sharded.fsdp_block_gatherer). The 'blocks' stack is STORED as
  # flat per-layer parameter shards ((L, k) locally; ops/sharded.py
  # fsdp_stacked_shards); each nn.scan iteration re-assembles ONE
  # block's full params with a packed all-gather INSIDE the scan body
  # (under nn.remat, so the backward re-gathers during recompute), and
  # the hook's custom_vjp backward reduce-scatters that block's
  # cotangent in the same position -- the full layer stack never
  # materializes. None = plain replicated-param storage. Only
  # meaningful with scan_layers; requires apply() to run inside a
  # shard_map body where the mesh axes are bound.
  fsdp_block_hook: Any = None
  max_len: int = SEQ_LEN
  dtype: Any = jnp.float32
  param_dtype: Any = jnp.float32
  # Serving (kf_benchmarks_tpu/serving/): decode=True switches
  # __call__ to the single-token KV-ring path -- (tokens (B,),
  # cache_k/cache_v (L, B, T, H, Dh), pos (B,)) -> (logits (B, 1, V),
  # (cache_k', cache_v')); return_kv=True makes the full-sequence
  # forward additionally return the stacked per-layer K/V projections
  # (the packed-prefill cache source). Both off = the exact legacy
  # program (golden contracts unchanged). decode_exact selects the
  # bit-identity oracle attention schedule over the ~T x cheaper 1-row
  # production one (sequence.decode_attention).
  decode: bool = False
  return_kv: bool = False
  decode_exact: bool = False
  # Paged KV decode (serving/decode.py paged mode): >0 makes the decode
  # path take (L, P, page, H, Dh) page POOLS plus a (B, pages_per_slot)
  # page table instead of the dense per-slot ring slab (the _Block
  # field of the same name). 0 = dense ring; the forward/training
  # program never sees it.
  kv_page_size: int = 0

  @nn.compact
  def __call__(self, tokens, cache_k=None, cache_v=None, pos=None,
               page_table=None):
    if self.decode:
      return self._decode_call(tokens, cache_k, cache_v, pos,
                               page_table)
    tokens = tokens.astype(jnp.int32)
    seg = positions = None
    if tokens.ndim == 3:
      # Packed input (--packed_sequences): the (B, 3, T) int32 stack
      # [tokens, segment_ids, positions] from data/packing.py. Shape
      # is the mode switch, so the module needs no config flag and
      # unpacked callers keep the exact legacy program.
      tokens, seg, positions = (tokens[:, 0], tokens[:, 1],
                                tokens[:, 2])
    b, t = tokens.shape
    block_kwargs = dict(
        d_model=self.d_model, n_heads=self.n_heads, d_ff=self.d_ff,
        attn_block=self.attn_block, attn_q_block=self.attn_q_block,
        attn_impl=self.attn_impl, dtype=self.dtype,
        param_dtype=self.param_dtype, return_kv=self.return_kv)

    x = nn.Embed(self.vocab, self.d_model, name="embed",
                 dtype=self.dtype, param_dtype=self.param_dtype)(tokens)
    pos = self.param(
        "pos_embedding",
        nn.initializers.normal(0.02, self.param_dtype),
        (self.max_len, self.d_model))
    if positions is None:
      x = x + pos[:t].astype(self.dtype)
    else:
      # Per-document positions (restart at 0 per segment): a packed
      # document reads the same position rows it would alone.
      x = x + jnp.take(pos, positions, axis=0).astype(self.dtype)

    if self.scan_layers:
      # One block body in the compiled program regardless of depth;
      # jax.checkpoint per block (nn.remat) keeps only the block
      # boundaries as backward residuals. prevent_cse=False is the
      # scan-safe setting (the scan barrier already blocks the CSE
      # that prevent_cse guards against; True pessimizes TPU code).
      block_cls = _Block
      if self.fsdp_block_hook is not None:
        # FSDP storage -> full block params, one packed all-gather per
        # scan iteration (ops/sharded.py gather_params). Init stays
        # full-shape and collective-free: the hook passes the empty
        # pre-creation store through, so module.init creates FULL
        # params under plain jit and the train step's init_state
        # re-stacks them into the shard layout host-side.
        block_cls = nn.map_variables(
            _Block, "params", trans_in_fn=self.fsdp_block_hook,
            init=True)
      blocks = nn.scan(
          nn.remat(block_cls, prevent_cse=False),
          variable_axes={"params": 0},
          split_rngs={"params": True},
          length=self.n_layers)(name="blocks", **block_kwargs)
      (x, _), kv = blocks((x, seg), None)
    else:
      kv_rows = []
      for i in range(self.n_layers):
        (x, _), kv_i = _Block(name=f"block_{i}", **block_kwargs)(
            (x, seg), None)
        kv_rows.append(kv_i)
      # Stack the per-layer K/V rows like nn.scan would, so the two
      # layer paths hand serving the same (L, B, T, H, Dh) layout.
      kv = (jnp.stack([r[0] for r in kv_rows]),
            jnp.stack([r[1] for r in kv_rows])) if self.return_kv \
          else None

    x = nn.LayerNorm(name="ln_f", dtype=jnp.float32,
                     param_dtype=self.param_dtype)(x)
    # The head computes in the model dtype: at 32k vocab an f32 logits
    # tensor is the HBM peak (measured OOM at bs=8 on 16 GB, PERF.md);
    # the loss upcasts per sequence chunk instead.
    w_head = self.param("lm_head", nn.initializers.lecun_normal(),
                        (self.d_model, self.vocab), self.param_dtype)
    aux = None
    if seg is not None:
      # Packed runs hand the per-token loss weights to the loss and
      # accuracy functions through the aux slot (the ONE derivation,
      # data/packing.py): 0 at padding and document-final slots.
      from kf_benchmarks_tpu.data import packing as packing_lib
      aux = packing_lib.token_weights_from_segments(seg)
    if self.fused_head:
      # No logits here at ALL: the head matmul itself is deferred into
      # the chunked loss/accuracy reductions (ops/fused_loss.py).
      out = fused_loss_lib.FusedLMHead(
          hidden=x.astype(self.dtype), kernel=w_head)
    else:
      out = x.astype(self.dtype) @ w_head.astype(self.dtype)
    if self.return_kv:
      return out, aux, kv
    return out, aux

  def _decode_call(self, tokens, cache_k, cache_v, pos, page_table=None):
    """The single-token KV-ring decode step (serving/decode.py).

    ``tokens`` (B,) int32 is each slot's CURRENT token at absolute
    position ``pos`` (B,); its K/V are written into the ring at
    ``pos % T`` and the returned (B, 1, V) logits predict position
    ``pos + 1``. Ring semantics: within the first ``max_len`` tokens
    the cache index IS the absolute position (and decode is
    bit-identical to the full-sequence forward); past it the buffer
    wraps and attention covers the trailing ``max_len``-token window.
    Always the dense head -- a (B, 1, V) logits row is microscopic
    next to the fused head's reason for existing.

    With ``kv_page_size`` set, ``cache_k``/``cache_v`` are the shared
    (L, P, page, H, Dh) page pools and ``page_table`` the per-slot
    (B, pages_per_slot) pool-row map; the table rides the scan carry
    (shared by every layer) while the pools stay the scanned
    input/output, so the layer structure is the dense branch's.
    """
    tok = tokens.astype(jnp.int32).reshape(-1, 1)
    b = tok.shape[0]
    block_kwargs = dict(
        d_model=self.d_model, n_heads=self.n_heads, d_ff=self.d_ff,
        attn_block=self.attn_block, attn_q_block=self.attn_q_block,
        attn_impl=self.attn_impl, dtype=self.dtype,
        param_dtype=self.param_dtype, decode=True,
        decode_exact=self.decode_exact,
        kv_page_size=self.kv_page_size)
    x = nn.Embed(self.vocab, self.d_model, name="embed",
                 dtype=self.dtype, param_dtype=self.param_dtype)(tok)
    pos_emb = self.param(
        "pos_embedding",
        nn.initializers.normal(0.02, self.param_dtype),
        (self.max_len, self.d_model))
    # Per-slot position row (ring-wrapped past max_len): the same table
    # row the full forward adds at that position.
    x = x + jnp.take(pos_emb, pos % self.max_len,
                     axis=0)[:, None, :].astype(self.dtype)
    if self.kv_page_size:
      carry_in = (x, pos, page_table.astype(jnp.int32))
    else:
      carry_in = (x, pos)
    if self.scan_layers:
      blocks = nn.scan(
          _Block,
          variable_axes={"params": 0},
          split_rngs={"params": True},
          length=self.n_layers)(name="blocks", **block_kwargs)
      carry_out, (ck, cv) = blocks(carry_in, (cache_k, cache_v))
      x = carry_out[0]
    else:
      cks, cvs = [], []
      carry = carry_in
      for i in range(self.n_layers):
        carry, (ck_i, cv_i) = _Block(name=f"block_{i}", **block_kwargs)(
            carry, (cache_k[i], cache_v[i]))
        cks.append(ck_i)
        cvs.append(cv_i)
      x = carry[0]
      ck, cv = jnp.stack(cks), jnp.stack(cvs)
    x = nn.LayerNorm(name="ln_f", dtype=jnp.float32,
                     param_dtype=self.param_dtype)(x)
    w_head = self.param("lm_head", nn.initializers.lecun_normal(),
                        (self.d_model, self.vocab), self.param_dtype)
    logits = x.astype(self.dtype) @ w_head.astype(self.dtype)
    return logits, (ck, cv)


class TransformerLMModel(model_lib.Model):
  """Decoder-only LM over synthetic token streams (no reference
  counterpart; the zoo's long-context member)."""

  def __init__(self, params=None):
    super().__init__("transformer_lm", batch_size=8, learning_rate=0.05,
                     fp16_loss_scale=128, params=params)
    # --packed_sequences: inputs become the (B, 3, T) packed stack and
    # losses/metrics weight by real-token count (data/packing.py).
    self.packed = bool(getattr(params, "packed_sequences", False)
                       ) if params is not None else False
    if self.packed:
      from kf_benchmarks_tpu.data import packing as packing_lib
      # The train step's token-weighted metric combine reads each
      # replica's real-label weights from the packed input stack
      # (images[:, 1] = segment ids) -- the same derivation the
      # module's aux weights use, so loss and metrics cannot drift.
      self.token_weight_fn = (
          lambda images: packing_lib.token_weights_from_segments(
              images[:, 1]))

  def make_module(self, nclass, phase_train, data_format="NHWC",
                  dtype=jnp.float32, param_dtype=jnp.float32):
    del nclass, data_format
    import os
    impl = os.environ.get("KF_TRANSFORMER_LM_ATTN", "tiled")
    if impl not in ("tiled", "flash"):
      raise ValueError(
          f"KF_TRANSFORMER_LM_ATTN must be 'tiled' or 'flash', got "
          f"{impl!r}")
    # The library flash kernel's pallas_call gives its outputs no vma
    # type, which the step's shard_map checker refuses ("`vma` on
    # `jax.ShapeDtypeStruct` must not be `None`", TPU v5e, PR 21): the
    # flash arm opts out of the checker like the other models built on
    # untyped library internals (train_step.py relax_shard_map_vma).
    self.relax_shard_map_vma = impl == "flash"
    head = os.environ.get("KF_TRANSFORMER_LM_HEAD", "fused")
    if head not in ("fused", "dense"):
      raise ValueError(
          f"KF_TRANSFORMER_LM_HEAD must be 'fused' or 'dense', got "
          f"{head!r}")
    layers = os.environ.get("KF_TRANSFORMER_LM_LAYERS", "scan")
    if layers not in ("scan", "loop"):
      raise ValueError(
          f"KF_TRANSFORMER_LM_LAYERS must be 'scan' or 'loop', got "
          f"{layers!r}")
    # --attn_block (validated against SEQ_LEN in validation.py): one
    # value drives BOTH tilings -- the K/V block and the matched
    # q-block -- so an autotuned size never confounds the two-level
    # schedule with mismatched tiles (the matched-tilings rule the
    # flash/tiled A/B already follows). None = the module defaults.
    attn_block = int(getattr(self.params, "attn_block", None) or 0) \
        if self.params is not None else 0
    # Scan-over-layers params carry a leading depth axis under 'blocks'
    # (PR 2): observability.SummaryWriter unstacks histogram keys per
    # layer via this attribute (tests/test_observability.py).
    self.scanned_param_prefixes = ("blocks",) if layers == "scan" else ()
    p = self.params
    # --shard_params (full FSDP): the scanned 'blocks' stack stores as
    # per-layer parameter shards and each scan iteration gathers ONE
    # block inside the loop body (ops/sharded.fsdp_block_gatherer).
    # fsdp_gathered_prefixes tells the step-level bucket gather
    # (train_step.py) these leaves are module-gathered. Training module
    # only: eval applies the PLAIN module to the step-gathered full
    # tree. The loop fallback needs no hook -- its per-layer 'block_i'
    # top keys are exactly the builder-layer buckets the step gathers.
    fsdp_block_hook = None
    if (phase_train and layers == "scan" and p is not None
        and getattr(p, "shard_params", False)):
      from kf_benchmarks_tpu.ops import sharded as sharded_lib
      from kf_benchmarks_tpu.parallel.mesh import BATCH_AXIS, MODEL_AXIS
      plain = _TransformerLMModule(dtype=dtype, param_dtype=param_dtype,
                                   attn_impl=impl,
                                   fused_head=head == "fused",
                                   scan_layers=True)
      sample = jnp.zeros(tuple(self.get_input_shapes("train")[0]),
                         jnp.int32)
      # Abstract init (nothing executes): one block's full shapes =
      # the stacked 'blocks' leaves with the leading layer axis
      # stripped -- the gather spec the hook re-assembles against.
      variables = jax.eval_shape(
          lambda: plain.init({"params": jax.random.PRNGKey(0),
                              "dropout": jax.random.PRNGKey(0)}, sample))
      block_template = jax.tree.map(
          lambda s: jax.ShapeDtypeStruct(tuple(s.shape)[1:], s.dtype),
          variables["params"]["blocks"])
      # --partitioner=gspmd traces the step under double vmap, which
      # has no tuple-axis all_gather batching rule (jax 0.9.0): the
      # hook's forward gather decomposes per axis there (element-
      # identical; ops/sharded.combined_all_gather).
      fsdp_block_hook = sharded_lib.fsdp_block_gatherer(
          block_template, BATCH_AXIS, MODEL_AXIS,
          nested=getattr(p, "partitioner", None) == "gspmd")
      self.fsdp_gathered_prefixes = ("blocks",)
    tiling = (dict(attn_block=attn_block, attn_q_block=attn_block)
              if attn_block else {})
    return _TransformerLMModule(dtype=dtype, param_dtype=param_dtype,
                                attn_impl=impl,
                                fused_head=head == "fused",
                                scan_layers=layers == "scan",
                                fsdp_block_hook=fsdp_block_hook,
                                **tiling)

  def get_input_shapes(self, subset):
    n = self.get_batch_size()
    if self.packed:
      # [tokens, segment_ids, positions] stacked (data/packing.py).
      return [[n, 3, SEQ_LEN], [n, SEQ_LEN]]
    return [[n, SEQ_LEN], [n, SEQ_LEN]]

  def get_input_data_types(self, subset):
    return [jnp.int32, jnp.int32]

  def get_synthetic_inputs(self, rng, nclass):
    n = self.get_batch_size()
    if self.packed:
      # One deterministic packed batch (direct callers / AOT; the
      # benchmark streams fresh batches through the DeviceFeeder
      # instead, benchmark.py _input_iterator).
      from kf_benchmarks_tpu.data import packing as packing_lib
      stream = packing_lib.PackedBatchStream(
          SEQ_LEN, n, VOCAB, seed=int(jax.random.randint(
              rng, (), 0, 2**31 - 1)))
      images, labels = next(stream)
      return jnp.asarray(images), jnp.asarray(labels)
    tokens = jax.random.randint(rng, (n, SEQ_LEN), 0, VOCAB, jnp.int32)
    # Next-token labels: the shifted stream, so the synthetic objective
    # is the real LM objective (learnable, not pure noise).
    labels = jnp.roll(tokens, -1, axis=1)
    return tokens, labels

  # Sequence-chunk size for the loss: the f32 softmax temps live one
  # chunk at a time ((B, 256, 32768) f32 = 268 MB at bs 8) instead of
  # the whole (B, T, V) tensor, and jax.checkpoint makes the backward
  # recompute per chunk rather than keep every chunk's softmax alive.
  LOSS_CHUNK = 256

  def loss_function(self, build_network_result, labels):
    # aux carries the packed per-token loss weights (the module derives
    # them from the segment ids); None on unpacked runs.
    out, weights = build_network_result.logits
    labels = labels.astype(jnp.int32)
    if isinstance(out, fused_loss_lib.FusedLMHead):
      # Fused head: loss straight from (hidden, kernel); no logits
      # tensor exists anywhere in the step (ops/fused_loss.py).
      return fused_loss_lib.fused_softmax_xent(
          out.hidden, out.kernel, labels, chunk_size=self.LOSS_CHUNK,
          weights=weights)
    # Dense-head fallback: logits are materialized; chunk the softmax
    # reduction only (the round-6 bounded-memory path).
    logits = out
    b, t, v = logits.shape
    chunk = fused_loss_lib.chunk_of(t, self.LOSS_CHUNK)
    lc = logits.reshape(b, t // chunk, chunk, v).swapaxes(0, 1)
    yc = labels.reshape(b, t // chunk, chunk).swapaxes(0, 1)
    wc = None if weights is None else weights.astype(
        jnp.float32).reshape(b, t // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
      lg, yy, ww = xs
      logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
      ll = jnp.take_along_axis(logp, yy[..., None], axis=-1)
      if ww is not None:
        ll = ll * ww[..., None]
      return carry + jnp.sum(ll), None

    (zero,) = sequence_lib.vary_like(logits,
                                     (jnp.zeros((), jnp.float32),))
    total, _ = jax.lax.scan(body, zero, (lc, yc, wc))
    if weights is None:
      return -total / (b * t)
    return -total / jnp.maximum(
        jnp.sum(weights.astype(jnp.float32)), 1.0)

  def accuracy_function(self, build_network_result, labels):
    out, weights = build_network_result.logits
    labels = labels.astype(jnp.int32)
    if isinstance(out, fused_loss_lib.FusedLMHead):
      return fused_loss_lib.fused_top_k_accuracy(
          out.hidden, out.kernel, labels, chunk_size=self.LOSS_CHUNK,
          weights=weights)
    logits = out
    # argmax/top_k reduce away the vocab axis chunk-free (no f32
    # upcast of the full logits tensor is ever materialised).
    hit1 = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    hit5 = jnp.any(jax.lax.top_k(logits, 5)[1] == labels[..., None],
                   axis=-1).astype(jnp.float32)
    if weights is None:
      return {"top_1_accuracy": jnp.mean(hit1),
              "top_5_accuracy": jnp.mean(hit5)}
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    return {"top_1_accuracy": jnp.sum(hit1 * w) / denom,
            "top_5_accuracy": jnp.sum(hit5 * w) / denom}


def create_transformer_lm_model(params=None):
  return TransformerLMModel(params=params)
