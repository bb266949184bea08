"""Transformer encoder / BERT-style models built from the layer DSL.

ref ``python/paddle/fluid/tests/unittests/dist_transformer.py:958,1034``
(multi_head_attention / scaled_dot_product_attention built from fluid.layers
— the BASELINE Transformer recipe) and the LARK BERT config (BASELINE.md).

TPU-first notes: everything is dense [batch, seq, d] (no LoD); attention is
plain batched matmul so XLA can fuse and the MXU takes the contractions.
``annotate_tensor_parallel`` marks the canonical Megatron layout on the
weights (QKV/FFN-in column-parallel, proj/FFN-out row-parallel) via
``Variable.dist_spec`` — under a mesh with an ``mp`` axis GSPMD inserts the
two all-reduces per layer; on a dp-only mesh the annotations are inert.
"""

from __future__ import annotations

import numpy as np

from .. import initializer, layers
from ..framework import default_main_program, name_scope
from ..param_attr import ParamAttr


def multi_head_attention(queries, keys, values, d_model, n_head,
                         dropout_rate=0.0, attn_bias=None, is_test=False,
                         param_prefix="attn", attn_impl="base",
                         causal=False, bias=True, n_kv_head=None,
                         qk_hook=None, d_head=None, window=None,
                         out_gate=False, head_hook=None,
                         block_diffusion=None):
    """ref dist_transformer.py:958 multi_head_attention.

    attn_impl: "base" (matmul→softmax→matmul chain, ref recipe),
    "flash" (fused Pallas kernel, O(T) memory), "ring"
    (sequence-parallel over the mesh's sp axis), or "auto" — flash when
    it's the measured winner (T ≥ 1024 on v5e, and exact semantics are
    preserved, i.e. no attention-weight dropout wanted), else base.
    Fused paths skip attention-weight dropout (standard for flash).

    ``bias=False``: no bias on any projection.  ``n_kv_head`` (default
    ``n_head``): K and V are projected to that many heads and each is
    shared by ``n_head // n_kv_head`` query heads.  ``qk_hook(q, k) ->
    (q, k)`` runs on the projected [b, t, heads * d_head] tensors before
    the head split (QK-norm over the whole projection, rotary embedding).

    ``d_head`` (default ``d_model // n_head``): the heads' width where the
    model gives it apart from ``d_model``; Q, the gate and the attention
    output are then ``n_head * d_head`` wide.  ``head_hook(q, k) -> (q, k)``
    runs after the head split, on [b, n_head, t, d_head] and [b, n_kv_head,
    t, d_head] (a QK-norm per head, weight ``[d_head]``, and the rotary
    embedding after it).  ``window`` (flash, causal): key ``j`` is visible
    to query ``i`` iff ``0 <= i - j < window``.  ``block_diffusion`` (flash,
    not causal): ``layers.flash_attention``'s.  ``out_gate=True``: a
    fourth slice ``[d_model, n_head * d_head]`` of the fused projection
    gates the attention output, ``ctx * sigmoid(gate)``, before the output
    projection (self-attention only).  On the flash path K and V reach the
    kernel at their ``n_kv_head`` heads; the other paths expand them.

    Q, K and V here are slices of one projection of the layer's input, all
    ``d_head`` wide.  Attention through low-rank latents, with scores wider
    than the values and a rotary slice shared by the heads, is another
    builder: :func:`latent_attention`.
    """
    d_head = d_head or d_model // n_head
    d_q = n_head * d_head
    n_kv_head = n_kv_head or n_head
    d_kv = n_kv_head * d_head
    if attn_impl == "auto":
        seq = queries.shape[1] if queries.shape is not None else 0
        exact = (dropout_rate == 0.0) or is_test
        attn_impl = "flash" if (seq and seq >= 1024 and exact) else "base"

    def _proj(x, size, name):
        return layers.fc(x, size=size, num_flatten_dims=2,
                         param_attr=ParamAttr(name=f"{param_prefix}.{name}.w"),
                         bias_attr=ParamAttr(name=f"{param_prefix}.{name}.b")
                         if bias else False)

    gate = None
    if queries is keys and keys is values:
        # self-attention: one fused QKV projection — bigger MXU tile, one
        # HBM read of the activations instead of three
        if out_gate:
            q, k, v, gate = layers.split(
                _proj(queries, 2 * d_q + 2 * d_kv, "qkv"),
                [d_q, d_kv, d_kv, d_q], dim=2)
        else:
            qkv = _proj(queries, d_q + 2 * d_kv, "qkv")
            q, k, v = layers.split(
                qkv, 3 if d_kv == d_q else [d_q, d_kv, d_kv], dim=2)
    else:
        assert not out_gate, "out_gate= is for self-attention"
        q = _proj(queries, d_q, "q")
        k = _proj(keys, d_kv, "k")
        v = _proj(values, d_kv, "v")
    if qk_hook is not None:
        q, k = qk_hook(q, k)
    # the flash kernels read grouped K/V heads through their index maps
    grouped = attn_impl == "flash" and n_kv_head != n_head
    assert window is None or attn_impl == "flash", "window= needs flash"
    assert not block_diffusion or attn_impl == "flash", \
        "block_diffusion= needs flash"

    def _split_heads(x, heads=n_head):
        # [b, t, d] -> [b, h, t, dh]
        y = layers.reshape(x, shape=[0, 0, heads, d_head])
        return layers.transpose(y, perm=[0, 2, 1, 3])

    def _expand_kv(y):
        # each K/V head serves n_head // n_kv_head query heads
        if n_kv_head == n_head or grouped:
            return y
        rep = n_head // n_kv_head
        y = layers.expand(layers.unsqueeze(y, [2]), [1, 1, rep, 1, 1])
        return layers.reshape(y, shape=[0, n_head, -1, d_head])

    q, k = _split_heads(q), _split_heads(k, n_kv_head)
    if head_hook is not None:
        q, k = head_hook(q, k)
    k = _expand_kv(k)
    v = _expand_kv(_split_heads(v, n_kv_head))
    if attn_impl == "flash":
        ctx = layers.flash_attention(q, k, v, bias=attn_bias, causal=causal,
                                     sm_scale=float(d_head) ** -0.5,
                                     window=window,
                                     block_diffusion=block_diffusion)
    elif attn_impl == "ring":
        assert attn_bias is None, "ring attention supports causal= only"
        ctx = layers.ring_attention(q, k, v, causal=causal,
                                    sm_scale=float(d_head) ** -0.5)
    else:
        # scaled dot-product attention (ref dist_transformer.py:1034)
        scores = layers.matmul(q, k, transpose_y=True,
                               alpha=float(d_head) ** -0.5)
        if attn_bias is not None:
            scores = scores + attn_bias
        if causal:
            # [T,T] additive mask built from ops (no tril op in the
            # registry): -1e9 where j > i, broadcast over [b,h,T,T]
            t = q.shape[2]
            r = layers.range(0, t, 1, "float32")
            row = layers.expand(layers.unsqueeze(r, [1]), [1, t])
            col = layers.expand(layers.unsqueeze(r, [0]), [t, 1])
            mask = layers.scale(layers.relu(layers.sign(col - row)),
                                scale=-1e9)
            scores = scores + mask
        weights = layers.softmax(scores)
        if dropout_rate:
            weights = layers.dropout(
                weights, dropout_prob=dropout_rate, is_test=is_test,
                dropout_implementation="upscale_in_train")
        ctx = layers.matmul(weights, v)                   # [b, h, t, dh]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, d_q])
    if gate is not None:
        ctx = ctx * layers.sigmoid(gate)
    return layers.fc(ctx, size=d_model, num_flatten_dims=2,
                     param_attr=ParamAttr(name=f"{param_prefix}.out.w"),
                     bias_attr=ParamAttr(name=f"{param_prefix}.out.b")
                     if bias else False)


def positionwise_ffn(x, d_inner, d_model, dropout_rate=0.0, is_test=False,
                     param_prefix="ffn", act="gelu"):
    h = layers.fc(x, size=d_inner, num_flatten_dims=2, act=act,
                  param_attr=ParamAttr(name=f"{param_prefix}.fc1.w"),
                  bias_attr=ParamAttr(name=f"{param_prefix}.fc1.b"))
    if dropout_rate:
        h = layers.dropout(h, dropout_prob=dropout_rate, is_test=is_test,
                           dropout_implementation="upscale_in_train")
    return layers.fc(h, size=d_model, num_flatten_dims=2,
                     param_attr=ParamAttr(name=f"{param_prefix}.fc2.w"),
                     bias_attr=ParamAttr(name=f"{param_prefix}.fc2.b"))


def encoder_layer(x, d_model, d_inner, n_head, dropout_rate=0.0,
                  attn_bias=None, is_test=False, idx=0, attn_impl="base",
                  causal=False):
    """post-LN residual block (ref dist_transformer encoder_layer)."""
    attn = multi_head_attention(x, x, x, d_model, n_head, dropout_rate,
                                attn_bias, is_test,
                                param_prefix=f"enc_{idx}.attn",
                                attn_impl=attn_impl, causal=causal)
    if dropout_rate:
        attn = layers.dropout(attn, dropout_prob=dropout_rate,
                              is_test=is_test,
                              dropout_implementation="upscale_in_train")
    x = layers.layer_norm(x + attn, begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"enc_{idx}.ln1.w"),
                          bias_attr=ParamAttr(name=f"enc_{idx}.ln1.b"))
    ffn = positionwise_ffn(x, d_inner, d_model, dropout_rate, is_test,
                           param_prefix=f"enc_{idx}.ffn")
    if dropout_rate:
        ffn = layers.dropout(ffn, dropout_prob=dropout_rate, is_test=is_test,
                             dropout_implementation="upscale_in_train")
    return layers.layer_norm(x + ffn, begin_norm_axis=2,
                             param_attr=ParamAttr(name=f"enc_{idx}.ln2.w"),
                             bias_attr=ParamAttr(name=f"enc_{idx}.ln2.b"))


def encoder(src_ids, pos_ids, vocab_size, max_pos, n_layer, d_model, d_inner,
            n_head, dropout_rate=0.0, attn_bias=None, is_test=False,
            type_ids=None, n_types=2, attn_impl="base", checkpoints=None,
            arange_pos=False, causal=False):
    """BERT-style embedding + N encoder layers.  Pass ``checkpoints=[]`` to
    collect each layer's output for RecomputeOptimizer (remat at layer
    boundaries — the standard transformer memory/compute trade).

    ``arange_pos=True``: positions are the canonical 0..T-1 for every row
    (always true in the pretrain recipe), so the position embedding is a
    static slice of the table broadcast over the batch — no [tokens]-sized
    gather forward and, more importantly, no scatter-add backward."""
    emb = layers.embedding(src_ids, size=[vocab_size, d_model],
                           param_attr=ParamAttr(name="word_embedding"))
    if arange_pos:
        seq_len = src_ids.shape[-1]
        pos_table = layers.create_parameter(
            [max_pos, d_model], dtype="float32",
            attr=ParamAttr(name="pos_embedding"))
        pos = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq_len])
        pos = layers.unsqueeze(pos, [0])          # [1, T, D] broadcast-add
    else:
        pos = layers.embedding(pos_ids, size=[max_pos, d_model],
                               param_attr=ParamAttr(name="pos_embedding"))
    x = emb + pos
    if type_ids is not None:
        x = x + layers.embedding(type_ids, size=[n_types, d_model],
                                 param_attr=ParamAttr(name="sent_embedding"))
    x = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name="pre_encoder.ln.w"),
                          bias_attr=ParamAttr(name="pre_encoder.ln.b"))
    if dropout_rate:
        x = layers.dropout(x, dropout_prob=dropout_rate, is_test=is_test,
                           dropout_implementation="upscale_in_train")
    for i in range(n_layer):
        x = encoder_layer(x, d_model, d_inner, n_head, dropout_rate,
                          attn_bias, is_test, idx=i, attn_impl=attn_impl,
                          causal=causal)
        if checkpoints is not None:
            checkpoints.append(x)
    return x


class BertConfig:
    """BERT-base defaults (BASELINE config #4)."""

    def __init__(self, vocab_size=30522, d_model=768, n_layer=12, n_head=12,
                 d_inner=3072, max_pos=512, dropout=0.1):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_layer = n_layer
        self.n_head = n_head
        self.d_inner = d_inner
        self.max_pos = max_pos
        self.dropout = dropout

    def num_params(self):
        V, D, L, F, P = (self.vocab_size, self.d_model, self.n_layer,
                         self.d_inner, self.max_pos)
        per_layer = 4 * D * D + 4 * D + 2 * D * F + F + D + 4 * D
        return V * D + P * D + 2 * D + L * per_layer


def _lm_head_loss(enc, cfg, lm_label, fused_head, param_name, bias=True,
                  table=None):
    """Shared LM head + masked-mean CE (label 0 = [PAD] excluded) used by
    the MLM and the causal-LM builders; ``bias=False`` for a bias-free
    head.  ``table``: the embedding's [vocab, d] parameter read as the
    head's weight (tied embeddings; no ``<param_name>.w`` exists then)."""
    w_attr = ParamAttr(name=f"{param_name}.w")
    b_attr = ParamAttr(name=f"{param_name}.b") if bias else False
    if fused_head:
        loss = layers.fused_lm_head_ce(
            enc, cfg.vocab_size, lm_label, param_attr=w_attr,
            bias_attr=b_attr, ignore_index=0, table=table)
        logits = None
    else:
        if table is not None:
            logits = layers.matmul(enc, table, transpose_y=True)
        else:
            logits = layers.fc(enc, size=cfg.vocab_size, num_flatten_dims=2,
                               param_attr=w_attr, bias_attr=b_attr)
        loss = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(lm_label, [2]), ignore_index=0)
    mask = layers.cast(lm_label > 0, "float32")
    masked = layers.reduce_sum(loss * layers.unsqueeze(mask, [2]))
    denom = layers.reduce_sum(mask) + 1e-6
    return logits, masked / denom


def build_bert_pretrain(cfg: BertConfig, seq_len, is_test=False,
                        dropout=None, attn_impl="base", fused_head=False,
                        checkpoints=None, arange_pos=False,
                        masked_gather=None):
    """Masked-LM pretraining net: ids+mask-labels → mean masked CE loss.

    Labels use 0 ([PAD], never a real MLM target) for unmasked positions;
    positions with label 0 are excluded from loss and denominator — the
    masked-LM objective of the LARK recipe.

    ``fused_head=True`` computes the head projection + CE with the chunked
    ``fused_lm_head_ce`` op: the [tokens, vocab] logits (GBs in f32 at
    vocab 30k) are never materialized, cutting the dominant HBM cost of the
    step; ``logits`` is returned as None in that mode.

    ``masked_gather=N``: the LARK/BERT recipe proper — feed ``mask_pos``
    ([b, N] flattened absolute positions, b_idx*seq+pos, exactly LARK's
    mask_pos feed) and ``lm_label`` [b, N]; the encoder output is gathered
    to the N masked positions per sequence BEFORE the head, so the
    [*, vocab] projection runs on ~15% of tokens.  The dense path (no
    gather) stays the default for the honest upper-bound config."""
    dropout = cfg.dropout if dropout is None else dropout
    src_ids = layers.data("src_ids", shape=[seq_len], dtype="int64")
    # arange_pos: positions come from a static table slice, so no pos_ids
    # feed exists at all (no dead input to synthesize and ship)
    pos_ids = None if arange_pos else \
        layers.data("pos_ids", shape=[seq_len], dtype="int64")
    label_len = masked_gather if masked_gather else seq_len
    lm_label = layers.data("lm_label", shape=[label_len], dtype="int64")
    mask_pos = layers.data("mask_pos", shape=[label_len], dtype="int64") \
        if masked_gather else None
    enc = encoder(src_ids, pos_ids, cfg.vocab_size, cfg.max_pos, cfg.n_layer,
                  cfg.d_model, cfg.d_inner, cfg.n_head, dropout,
                  is_test=is_test, attn_impl=attn_impl,
                  checkpoints=checkpoints, arange_pos=arange_pos)
    if masked_gather:
        flat = layers.reshape(enc, shape=[-1, cfg.d_model])
        enc = layers.reshape(
            layers.gather(flat, layers.reshape(mask_pos, shape=[-1])),
            shape=[-1, label_len, cfg.d_model])
    logits, avg_loss = _lm_head_loss(enc, cfg, lm_label, fused_head,
                                     "mlm_out")
    feeds = [src_ids] if arange_pos else [src_ids, pos_ids]
    if mask_pos is not None:
        feeds.append(mask_pos)
    feeds.append(lm_label)
    return tuple(feeds), logits, avg_loss


def build_gpt_pretrain(cfg: BertConfig, seq_len, is_test=False,
                       dropout=None, attn_impl="auto", fused_head=True,
                       checkpoints=None):
    """Decoder-only causal LM (GPT recipe): ids → causal transformer →
    next-token CE.  No reference counterpart (the 2019 snapshot has no
    decoder-only family) — TPU-native addition exercising the causal
    flash path at train time (attn_impl="auto" picks the Pallas kernel
    from T≥1024, where causal=True skips the masked key blocks outright,
    ~2× over a masked dense chain).

    ``lm_label`` is the next-token target (the input pipeline shifts;
    label 0 = [PAD] is excluded from loss, matching build_bert_pretrain's
    convention)."""
    dropout = cfg.dropout if dropout is None else dropout
    src_ids = layers.data("src_ids", shape=[seq_len], dtype="int64")
    lm_label = layers.data("lm_label", shape=[seq_len], dtype="int64")
    enc = encoder(src_ids, None, cfg.vocab_size, cfg.max_pos, cfg.n_layer,
                  cfg.d_model, cfg.d_inner, cfg.n_head, dropout,
                  is_test=is_test, attn_impl=attn_impl,
                  checkpoints=checkpoints, arange_pos=True, causal=True)
    logits, avg_loss = _lm_head_loss(enc, cfg, lm_label, fused_head,
                                     "lm_out")
    return (src_ids, lm_label), logits, avg_loss


def build_gpt_serving(cfg: BertConfig, seq_len, attn_impl="auto"):
    """Inference-only causal LM: ids → next-token logits, no label feed
    and no loss — the program a serving bucket factory materializes per
    sequence-length bucket (``paddle_tpu.serving.InferenceServer``).
    Parameter names match :func:`build_gpt_pretrain` exactly (shared
    ``lm_out`` head), so a trained scope serves unchanged."""
    src_ids = layers.data("src_ids", shape=[seq_len], dtype="int64")
    enc = encoder(src_ids, None, cfg.vocab_size, cfg.max_pos, cfg.n_layer,
                  cfg.d_model, cfg.d_inner, cfg.n_head, 0.0,
                  is_test=True, attn_impl=attn_impl, arange_pos=True,
                  causal=True)
    logits = layers.fc(enc, size=cfg.vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name="lm_out.w"),
                       bias_attr=ParamAttr(name="lm_out.b"))
    return (src_ids,), logits


# -- Decoders: one block, assembled from the parts a configuration names, ----
# -- and one causal-LM loop over it ------------------------------------------

# Down here because the lines above must stay where they are: where
# ``_lm_head_loss`` calls ``fused_lm_head_ce`` is a source location in every
# cell's lowered step, and the compile cache keys on that text (ROADMAP D13).
import contextlib  # noqa: E402


class DecoderConfig:
    """What :func:`decoder_block`, :func:`routed_ffn` and the causal-LM loop
    read off a decoder's configuration, with the values most of the classes
    below share.  A class sets what its model has otherwise: the family's
    constants in its body, a row's numbers in its constructor.  A decoder
    made of parts that exist is such a class and an entry point; a new
    mixer is its function and one entry of ``MIXERS``.

    ``mixer(idx)`` names the sequence mixer of layer ``idx``: ``"gqa"``
    (:func:`grouped_query_attention`, by the keys below), a key of
    ``MIXERS`` (``"mla"``, ``"kda"``, ``"conv"``, ``"mamba2"``) or None;
    ``ffn(idx)`` its FFN: ``"dense"``, ``"routed"`` (:func:`routed_ffn`) or
    None.  A block with both is the pre-norm pair ``h = x + Mixer(RMS(x))``,
    ``out = h + FFN(RMS(h))``; a block with one is that half alone.

    The stream: ``mup``, the embedding times ``sqrt(d_model)``;
    ``tie_embeddings``, the head reads the embedding table and there is no
    ``lm_out.w``.  The block: ``sandwich_norm``, a second norm on every
    sublayer's output, its terms summed before it.  Grouped-query attention:
    ``d_head`` (None: ``d_model // n_head``); ``qk_norm_over``, what Q and K
    are RMS-normed over, ``"projection"`` (before the head split, one
    weight for all heads), ``"head"`` (weight ``[d_head]``) or None;
    ``rotary(idx)``, whether the layer turns Q and K (rotate-half, after
    the norm; by default wherever there is a ``rope_theta``);
    ``window_at(idx)``, the keys the layer sees back (None: the whole causal
    half); ``block_diffusion``, the block length where the stream is a noisy
    copy beside a clean copy of each sequence under block diffusion's mask
    (None: causal); ``out_gate``, the output times ``sigmoid`` of a fourth slice of
    the fused projection; ``mixer_tags``, the ``name_scope`` of a mixer that
    does not tag itself (the per-layer metrics read the tags).  The FFNs:
    the first ``n_dense_layer`` layers ``"dense"`` (width ``d_inner``, the
    ``dense_ffn`` tag), the others ``"routed"``; ``gated``, SiLU-gated
    (:func:`gated_ffn`) or un-gated ReLU^2 (:func:`relu2_ffn`), the experts
    alike; ``d_shared``, the shared expert's width (None: none);
    ``score_func``, ``select_bias`` (a selection bias held at zero: the
    published recipes balance load through it, not through a loss term),
    ``route_norm`` and ``route_norm_eps`` (the kept scores renormalised, ``+
    eps``), ``route_scale``, ``n_route_group`` and ``topk_group``
    (group-limited selection), ``act`` (the gated experts' activation),
    ``init_std``: ``layers.moe_ffn``'s; ``router_before_mixer``, the router
    scores the block's normed input, before the mixer, and the experts read
    the FFN's; ``n_held``/``expert_offset``, the experts whose weights this
    program holds (default all): a chip's share under expert parallelism,
    see ``ops/moe_ops.py``.

    Two keys hold an order of independent ops and not a model's arithmetic;
    each value is an accepted cell's lowered step, and one value for all is
    a pair on the chip away (ROADMAP D19): ``qk_in_turn``, Q through norm
    and rotary, then K (False: both normed, then both turned);
    ``tag_covers_add``, the parts (a mixer's kind, ``"dense"``) whose tag
    also covers the residual add behind them."""

    mup = False
    tie_embeddings = False
    sandwich_norm = False
    qk_norm_over = None
    rope_theta = None
    out_gate = False
    block_diffusion = None
    mixer_tags = {"gqa": "attn", "conv": "conv_operator"}
    n_dense_layer = 0
    gated = True
    d_shared = None
    score_func = "sigmoid"
    select_bias = True
    route_norm = True
    route_norm_eps = 1e-20
    route_scale = 1.0
    n_route_group = 1
    topk_group = 1
    act = "silu"
    router_before_mixer = False
    qk_in_turn = True
    tag_covers_add = ()

    def __init__(self, vocab_size, d_model, n_layer, n_head, d_expert,
                 n_experts, top_k, rms_eps, n_held=None, expert_offset=0,
                 init_std=0.02, n_kv_head=None, d_head=None):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_layer = n_layer
        self.n_head = n_head
        self.n_kv_head = n_kv_head or n_head
        self.d_head = d_head
        self.d_expert = d_expert
        self.n_experts = n_experts
        self.top_k = top_k
        self.rms_eps = rms_eps
        self.n_held = n_experts if n_held is None else n_held
        self.expert_offset = expert_offset
        self.init_std = init_std

    def mixer(self, idx):
        return "gqa"

    def ffn(self, idx):
        return "dense" if idx < self.n_dense_layer else "routed"

    def rotary(self, idx):
        return self.rope_theta is not None

    def window_at(self, idx):
        return None


def gated_ffn(x, d_inner, d_model, param_prefix="ffn"):
    """``down(silu(gate(x)) * up(x))`` out of the dense ops, no bias: gate
    and up are one fused ``[d_model, 2 * d_inner]`` projection
    (``<prefix>.gate_up.w``, gate first), down is ``<prefix>.down.w``."""
    gu = layers.fc(x, size=2 * d_inner, num_flatten_dims=2, bias_attr=False,
                   param_attr=ParamAttr(name=f"{param_prefix}.gate_up.w"))
    g, u = layers.split(gu, 2, dim=2)
    return layers.fc(layers.swish(g) * u, size=d_model, num_flatten_dims=2,
                     bias_attr=False,
                     param_attr=ParamAttr(name=f"{param_prefix}.down.w"))


def relu2_ffn(x, d_inner, d_model, param_prefix="ffn"):
    """``down(relu(up(x))^2)`` out of the dense ops, no gate branch, no bias
    (``<prefix>.up.w``, ``<prefix>.down.w``)."""
    u = layers.fc(x, size=d_inner, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name=f"{param_prefix}.up.w"))
    return layers.fc(layers.square(layers.relu(u)), size=d_model,
                     num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=f"{param_prefix}.down.w"))


def plain_residual(x, sublayer, name):
    """The plain residual rule, ``x + F(norm(x))``: ``sublayer(x)`` norms its
    input and returns ``F``'s terms, added to ``x`` one by one, in order."""
    for term in sublayer(x):
        x = x + term
    return x


def hyper_connection(cfg: XingConfig):
    """The residual rule of a stream ``cfg.hc_mult`` wide, held as a list
    of that many [b, t, d] variables (manifold-constrained
    hyper-connections, arXiv:2512.24880 §4; ``layers.hc_pre`` /
    ``layers.hc_post``): the sublayer reads ``u``, a learned token-dependent
    mix of the streams, and its output is written back to every stream
    beside a doubly stochastic mix of the streams themselves.  Parameters
    ``<name>.phi``, ``.alpha``, ``.bias``, one set a sublayer."""
    def rule(x, sublayer, name):
        u, h_post, h_res = layers.hc_pre(
            x, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.rms_eps,
            cfg.hc_res_clamp, param_prefix=name)
        y = None
        for term in sublayer(u):
            y = term if y is None else y + term
        return layers.hc_post(x, y, h_post, h_res, cfg.hc_sinkhorn_iters)
    return rule


# -- OLMoE: pre-norm decoder with QK-norm, rotary and a sparse-expert FFN ----

class OlmoeConfig(DecoderConfig):
    """OLMoE-1B-7B defaults (``allenai/OLMoE-1B-7B-0125-Instruct``
    config.json); the loss coefficients are the training recipe's
    (arXiv:2409.02060).

    The block: ``h = x + Attn(RMSNorm(x))``, ``out = h +
    MoE(RMSNorm(h))``; no bias anywhere.  Q and K are RMS-normed over the
    whole projection before the head split, then rotated.  Every layer's
    FFN is ``n_experts`` routed experts of width ``d_expert`` (``top_k`` a
    token, softmax scores, no selection bias, no shared expert)."""

    qk_norm_over = "projection"
    mixer_tags = {}
    score_func = "softmax"
    select_bias = False
    route_norm_eps = 0.0

    def __init__(self, vocab_size=50304, d_model=2048, n_layer=16, n_head=16,
                 n_kv_head=None, d_expert=1024, n_experts=64, top_k=8,
                 norm_topk_prob=False, rms_eps=1e-5, rope_theta=10000.0,
                 lb_coef=0.01, z_coef=0.001, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, init_std=init_std,
                         n_kv_head=n_kv_head)
        self.route_norm = norm_topk_prob
        self.rope_theta = rope_theta
        self.lb_coef = lb_coef
        self.z_coef = z_coef


def build_olmoe_pretrain(cfg: OlmoeConfig, seq_len, is_test=False,
                         attn_impl="flash", fused_head=True):
    """:func:`_causal_lm` over :class:`OlmoeConfig`'s blocks.  Loss = its
    mean next-token CE + ``lb_coef`` * mean over layers of the
    load-balancing loss + ``z_coef`` * mean over layers of the router
    z-loss.  Returns ``(feeds, parts, loss)``, ``parts`` with "ce", "lb" and
    "z" beside what the loop gives."""
    feeds, parts, ce, aux = _causal_lm(cfg, seq_len, attn_impl, is_test,
                                       fused_head)
    lbs, zs = zip(*aux)
    lb = layers.sum(list(lbs)) / float(cfg.n_layer)
    z = layers.sum(list(zs)) / float(cfg.n_layer)
    loss = ce + cfg.lb_coef * lb + cfg.z_coef * z
    return feeds, dict(parts, ce=ce, lb=lb, z=z), loss


# -- Trinity (afmoe): window and full attention mixed, gated, per-head -------
# -- QK-norm, grouped-query; sigmoid routing beside a shared expert ----------

class TrinityConfig(DecoderConfig):
    """Trinity-Mini defaults (``arcee-ai/Trinity-Mini`` config.json,
    ``model_type`` ``afmoe``).  One afmoe block, four norms: ``h = x +
    RMS2(Attn(RMS1(x)))``, ``out = h + RMS4(FFN(RMS3(h)))``; no bias
    anywhere.  ``layer_types[i]`` is ``sliding_attention`` or
    ``full_attention``.  Attention: grouped-query, Q and K RMS-normed per
    head (weights ``[d_head]``), rotary on ``sliding_attention`` layers only
    (``full_attention`` layers carry no positional term), a ``window`` on
    the sliding layers, and the output gated by ``sigmoid`` of a fourth
    slice of the fused projection.  FFN: :func:`gated_ffn` of width
    ``d_inner`` in the first ``n_dense_layer`` layers; else one shared
    expert (the same builder, width ``d_expert * n_shared``) plus
    ``moe_ffn`` over ``n_experts`` routed experts of width ``d_expert``
    (``top_k`` a token) with sigmoid scores, a selection bias held at zero,
    the kept scores renormalised (``+ 1e-20``) and scaled."""

    sandwich_norm = True
    qk_norm_over = "head"
    out_gate = True
    mixer_tags = {}
    qk_in_turn = False

    def __init__(self, vocab_size=200192, d_model=2048, n_layer=32,
                 n_head=32, n_kv_head=4, d_head=128, d_inner=6144,
                 d_expert=1024, n_experts=128, top_k=8, n_shared=1,
                 n_dense_layer=2, layer_types=None, window=2048,
                 score_func="sigmoid", route_norm=True, route_scale=2.826,
                 rms_eps=1e-5, rope_theta=10000.0, mup=True, n_held=None,
                 expert_offset=0, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.d_inner = d_inner
        self.n_shared = n_shared
        self.d_shared = d_expert * n_shared
        self.n_dense_layer = n_dense_layer
        self.layer_types = list(layer_types) if layer_types else [
            "full_attention" if i % 4 == 3 else "sliding_attention"
            for i in range(n_layer)]
        assert len(self.layer_types) == n_layer
        self.window = window
        self.score_func = score_func
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.rope_theta = rope_theta
        self.mup = mup

    def rotary(self, idx):
        return self.layer_types[idx] == "sliding_attention"

    def window_at(self, idx):
        return self.window if self.rotary(idx) else None     # the same layers


def build_trinity_pretrain(cfg: TrinityConfig, seq_len, is_test=False,
                           attn_impl="flash", fused_head=True,
                           checkpoints=None):
    """:func:`_causal_lm` over :class:`TrinityConfig`'s afmoe blocks, the
    embedding scaled by ``sqrt(d_model)`` (``mup``); loss = its mean
    next-token CE and nothing else: the published recipe balances load
    through the selection bias, which is a persistent variable held at its
    initial zero here, not through a loss term.  ``checkpoints=[]`` collects
    the block outputs.  Returns its ``(feeds, parts, loss)``."""
    return _causal_lm(cfg, seq_len, attn_impl, is_test, fused_head,
                      checkpoints)[:3]


# -- JoyAI-LLM-Flash (DeepSeek-V3 family): latent attention, one multi-token --
# -- prediction module over the shared embedding and head ---------------------

class JoyaiConfig(DecoderConfig):
    """JoyAI-LLM-Flash defaults (``jdopensource/JoyAI-LLM-Flash``
    config.json, ``model_type`` ``joyai_llm_flash``, the DeepSeek-V3 family's
    keys).  The block, two norms: ``h = x + MLA(RMS1(x))``, ``out = h +
    FFN(RMS2(h))``; no bias anywhere.  Latent attention
    (:func:`latent_attention`): Q through a ``q_lora_rank`` latent, K's
    content part and V through a ``kv_lora_rank`` latent, ``d_nope +
    d_rope`` wide scores over ``d_v`` wide values, the rotary slice on
    adjacent pairs and its key one head for all.  FFN: :func:`gated_ffn` of
    width ``d_inner`` in the first ``n_dense_layer`` layers; else the one
    shared expert (the same builder, width ``d_expert``) plus ``moe_ffn``
    over ``n_experts`` routed experts of width ``d_expert`` as Trinity's
    block calls it (``noaux_tc`` with one group: ``top_k`` a token, sigmoid
    scores, a selection bias held at zero, the kept scores renormalised
    with ``1e-20`` and scaled).  ``n_mtp`` multi-token-prediction modules
    (0 or 1) follow the last layer.  What the family's later members add
    is :class:`XingConfig`'s; here it is absent: ``hc_mult`` 1 (the
    residual is the plain add) and ``rope_scaling`` None (frequencies
    ``rope_theta^(-2i / d_rope)``, the softmax scale ``(d_nope +
    d_rope)^-1/2``)."""

    hc_mult = 1
    rope_scaling = None

    def __init__(self, vocab_size=129280, d_model=2048, n_layer=40,
                 n_head=32, q_lora_rank=1536, kv_lora_rank=512, d_nope=128,
                 d_rope=64, d_v=128, d_inner=7168, d_expert=768,
                 n_experts=256, top_k=8, n_dense_layer=1, n_mtp=1,
                 route_scale=2.5, rms_eps=1e-6, rope_theta=32000000.0,
                 n_held=None, expert_offset=0):
        assert n_mtp in (0, 1), "one multi-token-prediction depth at most"
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset)
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.d_nope = d_nope
        self.d_rope = d_rope
        self.d_v = d_v
        self.d_inner = d_inner
        self.d_shared = d_expert
        self.n_dense_layer = n_dense_layer
        self.n_mtp = n_mtp
        self.route_scale = route_scale
        self.rope_theta = rope_theta

    def mixer(self, idx):
        return "mla"


class XingConfig(JoyaiConfig):
    """Xing4.0-29B-A4B defaults (``XingChen-AGI/Xing4.0-29B-A4B``
    config.json, ``model_type`` ``xing4_0``): the DeepSeek-V3 family's
    sublayers as :class:`JoyaiConfig` has them, and two things round them.
    The residual stream is ``hc_mult`` streams wide and every sublayer sits
    in a manifold-constrained hyper-connection (:func:`hyper_connection`;
    ``hc_sinkhorn_iters``, ``hc_eps`` and ``hc_res_clamp`` =
    ``(mhc_h_res_clamp_min, mhc_h_res_clamp_max)`` are its Sinkhorn-Knopp's).
    ``rope_scaling`` is the configuration's YaRN group: the rotary slice
    turns by a per-pair frequency table and the softmax scale is multiplied
    by ``(0.1 mscale_all_dim ln(factor) + 1)^2``.  No multi-token-prediction
    module over a widened stream: ``n_mtp`` has to be 0 with ``hc_mult`` >
    1."""

    YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}

    def __init__(self, vocab_size=131072, d_model=3584, n_layer=40,
                 n_head=32, q_lora_rank=768, kv_lora_rank=512, d_nope=128,
                 d_rope=64, d_v=128, d_inner=9216, d_expert=1024,
                 n_experts=64, top_k=4, n_dense_layer=2, n_mtp=0,
                 route_scale=2.0, rms_eps=1e-6, rope_theta=10000.0,
                 n_held=None, expert_offset=0, hc_mult=4,
                 hc_sinkhorn_iters=20, hc_eps=1e-6,
                 hc_res_clamp=(-30.0, 30.0), rope_scaling=YARN):
        super().__init__(
            vocab_size=vocab_size, d_model=d_model, n_layer=n_layer,
            n_head=n_head, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, d_nope=d_nope, d_rope=d_rope,
            d_v=d_v, d_inner=d_inner, d_expert=d_expert,
            n_experts=n_experts, top_k=top_k, n_dense_layer=n_dense_layer,
            n_mtp=n_mtp, route_scale=route_scale, rms_eps=rms_eps,
            rope_theta=rope_theta, n_held=n_held,
            expert_offset=expert_offset)
        assert hc_mult == 1 or not n_mtp, \
            "how the MTP module reads a widened stream is in no source"
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.hc_res_clamp = (float(hc_res_clamp[0]), float(hc_res_clamp[1]))
        self.rope_scaling = None if rope_scaling is None \
            else dict(rope_scaling)


def build_joyai_pretrain(cfg: JoyaiConfig, seq_len, mtp_weight=0.3,
                         checkpoints=None, fused_head=True):
    """:func:`_causal_lm` over :class:`JoyaiConfig`'s blocks (``L_main`` its
    mean CE against ``lm_label``, token ``i + 1``; ``z`` its final norm's
    output) with one multi-token-prediction module (arXiv:2412.19437 §2.2,
    depth 1).  With ``cfg.n_mtp``: ``u = [RMS_e(E[lm_label]) | RMS_h(z)]
    W_eh``, one whole expert-layer block over ``u`` (positions ``0 .. T -
    1``; the family numbers it ``n_layer``, past the dense layers), a norm,
    and THE SAME head: ``L_mtp`` = mean CE against ``mtp_label`` (token ``i
    + 2``).  ``E`` (``word_embedding``) and the head (``lm_out.w``) are the
    main model's parameters, read a second time by name, so each one's
    gradient is the sum of its two uses.  Loss = ``L_main + mtp_weight *
    L_mtp`` and nothing else (the selection bias is held at zero, as in
    :func:`build_trinity_pretrain`).  The module lies under the ``mtp``
    tag.  With ``cfg.hc_mult`` > 1 (:class:`XingConfig`) the stream between
    the blocks is ``hc_mult`` variables [b, t, d_model] and the blocks'
    residual rule is :func:`hyper_connection`; its entry and exit are here
    (arXiv:2409.19606 §3): the embedding copied to every stream, and the
    streams' sum before the final norm.  ``checkpoints=[]`` collects the
    block outputs, the module's among them.  Returns ``(feeds, parts,
    loss)``, ``parts`` with the module's "expert_load" last and
    "mtp_hidden" (the module's normed output), "main_loss" and "mtp_loss"
    beside what the loop gives."""
    wide = {}
    if cfg.hc_mult > 1:
        # a variable a stream: the same embedding read hc_mult times
        wide = dict(
            residual=hyper_connection(cfg), leave=layers.sums,
            enter=lambda x: [layers.scale(x, scale=1.0)
                             for _ in range(cfg.hc_mult)])
    feeds, parts, main_loss, _ = _causal_lm(
        cfg, seq_len, fused_head=fused_head, checkpoints=checkpoints, **wide)
    z = parts["hidden"]
    parts["main_loss"] = loss = main_loss
    if cfg.n_mtp:
        lm_label = feeds[1]
        mtp_label = layers.data("mtp_label", shape=[seq_len], dtype="int64")
        feeds += (mtp_label,)
        with name_scope("mtp"):
            e = layers.embedding(
                lm_label, size=[cfg.vocab_size, cfg.d_model],
                param_attr=ParamAttr(name="word_embedding"))
            u = layers.fc(
                layers.concat([_rms(e, cfg, "mtp_0.enorm"),
                               _rms(z, cfg, "mtp_0.hnorm")], axis=2),
                size=cfg.d_model, num_flatten_dims=2, bias_attr=False,
                param_attr=ParamAttr(name="mtp_0.eh_proj.w"))
            u, routed = decoder_block(u, cfg, cfg.n_layer,
                                      param_prefix="mtp_0")
            if checkpoints is not None:
                checkpoints.append(u)
            s = _rms(u, cfg, "mtp_0.shared_head_norm")
            _, mtp_loss = _lm_head_loss(s, cfg, mtp_label, fused_head,
                                        "lm_out", bias=False)
        parts["expert_load"].append(routed[2])
        parts.update(mtp_hidden=s, mtp_loss=mtp_loss)
        loss = main_loss + float(mtp_weight) * mtp_loss
    return feeds, parts, loss


# -- SmallThinker: a router that reads the layer's input before attention, ----
# -- ReLU-gated experts, window layers with rotary, full layers without -------

class SmallThinkerConfig(DecoderConfig):
    """SmallThinker-21BA3B-Instruct defaults
    (``PowerInfer/SmallThinker-21BA3B-Instruct`` config.json).  Every layer
    is an expert layer.  One block, two norms, no bias anywhere: ``n =
    RMS1(x)``; the router scores ``n``, the layer's input, BEFORE attention
    (six of 64, softmax over the six kept logits); ``h = x + Attn(n)``
    (grouped-query, no QK-norm, no gate); ``out = h + sum_e p_e Wd_e
    (relu(Wg_e m) * Wu_e m)`` over ``m = RMS2(h)``: the scores are taken
    before attention and consumed after it, so ``moe_ffn`` gets
    ``router_x=n`` beside its rows ``m``.  ``sliding_window_layout[i]`` 1:
    layer ``i`` sees ``window`` keys back, 0: the whole causal half;
    ``rope_layout[i]`` 1: rotate-half rotary on Q and K, 0: no positional
    term (published: the two layouts are one, full layers first of every
    four)."""

    score_func = "softmax"
    select_bias = False
    route_norm_eps = 0.0
    act = "relu"
    router_before_mixer = True
    tag_covers_add = ("gqa",)

    def __init__(self, vocab_size=151936, d_model=2560, n_layer=52,
                 n_head=28, n_kv_head=4, d_head=128, d_expert=768,
                 n_experts=64, top_k=6, window=4096,
                 sliding_window_layout=None, rope_layout=None,
                 rms_eps=1e-6, rope_theta=1.5e6, n_held=None,
                 expert_offset=0, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.window = window
        self.sliding_window_layout = list(sliding_window_layout) \
            if sliding_window_layout is not None else \
            [int(i % 4 != 0) for i in range(n_layer)]
        self.rope_layout = list(rope_layout) if rope_layout is not None \
            else list(self.sliding_window_layout)
        assert len(self.sliding_window_layout) == n_layer
        assert len(self.rope_layout) == n_layer
        self.rope_theta = rope_theta

    def rotary(self, idx):
        return bool(self.rope_layout[idx])

    def window_at(self, idx):
        return self.window if self.sliding_window_layout[idx] else None


def build_smallthinker_pretrain(cfg: SmallThinkerConfig, seq_len,
                                is_test=False, attn_impl="flash",
                                fused_head=True, checkpoints=None):
    """:func:`_causal_lm` over :class:`SmallThinkerConfig`'s blocks; loss =
    its mean next-token CE and nothing else (``config.json`` names no
    auxiliary loss).  ``checkpoints=[]`` collects the block outputs.
    Returns its ``(feeds, parts, loss)``."""
    return _causal_lm(cfg, seq_len, attn_impl, is_test, fused_head,
                      checkpoints)[:3]


# -- LFM2 (lfm2_moe): gated short-convolution operators beside grouped-query --
# -- attention, sigmoid routing, one table for the embedding and the head -----

class Lfm2Config(DecoderConfig):
    """LFM2-8B-A1B defaults (``LiquidAI/LFM2-8B-A1B`` config.json,
    ``model_type`` ``lfm2_moe``).  One lfm2_moe block, pre-norm, two norms,
    no bias anywhere: ``h = x + Op(RMS1(x))``, ``out = h + FF(RMS2(h))``.
    ``Op``: where ``layer_types[i]`` says ``full_attention``, grouped-query
    attention with Q and K RMS-normed per head (weights ``[d_head]``) and
    then rotated (rotate-half), under the ``attention_operator`` tag; where
    it says ``conv``, :func:`short_conv_operator` (the gated short
    convolution of ``conv_taps`` taps) under ``conv_operator``.  ``FF``:
    :func:`gated_ffn` of width ``d_inner`` in the first ``n_dense_layer``
    layers; else ``moe_ffn`` over ``n_experts`` routed experts of width
    ``d_expert`` (``top_k`` a token) with sigmoid scores, a selection bias
    held at zero, the kept scores renormalised (``+ 1e-6``) and scaled, no
    shared expert.  The head reads the embedding table."""

    tie_embeddings = True
    qk_norm_over = "head"
    mixer_tags = {"gqa": "attention_operator", "conv": "conv_operator"}
    route_norm_eps = 1e-6
    tag_covers_add = ("dense",)

    def __init__(self, vocab_size=65536, d_model=2048, n_layer=24, n_head=32,
                 n_kv_head=8, d_head=64, d_inner=7168, d_expert=1792,
                 n_experts=32, top_k=4, n_dense_layer=2, layer_types=None,
                 conv_taps=3, route_scale=1.0, rms_eps=1e-5, rope_theta=1e6,
                 n_held=None, expert_offset=0, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.d_inner = d_inner
        self.n_dense_layer = n_dense_layer
        # published: attention at layers 2, 6, 10, 14, 18 and 21 of 24
        self.layer_types = list(layer_types) if layer_types else [
            "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
            for i in range(n_layer)]
        assert len(self.layer_types) == n_layer
        self.conv_taps = conv_taps
        self.route_scale = route_scale
        self.rope_theta = rope_theta

    def mixer(self, idx):
        return "gqa" if self.layer_types[idx] == "full_attention" else "conv"


def build_lfm2_pretrain(cfg: Lfm2Config, seq_len, is_test=False,
                        attn_impl="flash", fused_head=True, checkpoints=None):
    """:func:`_causal_lm` over :class:`Lfm2Config`'s blocks, the logits
    over the embedding table itself (there is no ``lm_out.w``); loss = its
    mean next-token CE and nothing else (the selection bias is held at
    zero, as in :func:`build_trinity_pretrain`).  ``checkpoints=[]``
    collects the block outputs.  Returns its ``(feeds, parts, loss)``."""
    return _causal_lm(cfg, seq_len, attn_impl, is_test, fused_head,
                      checkpoints)[:3]


# -- Solar Open 2 (solar_open2; Kimi Linear's layer): gated delta-rule linear --
# -- attention 3:1 with gated position-free grouped-query attention, sigmoid ----
# -- routing beside a shared expert; a chip may hold a share of a layer's heads -

class SolarOpen2Config(DecoderConfig):
    """Solar-Open2-250B defaults (``upstage/Solar-Open2-250B`` config.json,
    ``model_type`` ``solar_open2``; the linear-attention layer is Kimi
    Linear's KDA, arXiv:2510.26692).  One solar_open2 block, pre-norm, two
    norms, no bias anywhere: ``h = x + Mixer(RMS1(x))``, ``out = h +
    Shared(RMS2(h)) + MoE(RMS2(h))``.  ``Mixer``: in ``gqa_layers``
    grouped-query softmax attention over the whole causal half with NO
    positional term (``use_rope`` false) and the output gated by
    ``sigmoid`` of a fourth slice of the fused projection
    (``use_gqa_gate``), under the ``attn`` tag, at the ``n_head`` over
    ``n_kv_head`` heads held here (the fused projection is ``[d_model, (2
    n_head + 2 n_kv_head) d_head]`` and the output projection ``[n_head
    d_head, d_model]``: narrower than ``d_model`` where a chip holds a
    share); else :func:`kda_attention`.  ``MoE``, in every layer:
    ``moe_ffn`` over ``n_experts`` routed experts of width ``d_expert``
    (``top_k`` a token) with sigmoid scores, a selection bias held at zero,
    the kept scores renormalised (``+ 1e-20``) and scaled; ``Shared``:
    :func:`gated_ffn` of width ``d_expert * n_shared``.

    ``n_head``, ``n_kv_head`` and ``n_kda_head`` are the heads this program
    HOLDS, default all 64, 8 and 64: under tensor parallelism a chip holds a
    share of a layer's heads, every head at its published ``d_head``, and
    the output projections give the partial sum over the held heads (the
    all-reduce is the deployment's)."""

    out_gate = True

    def __init__(self, vocab_size=196608, d_model=4096, n_layer=48,
                 n_head=64, n_kv_head=8, n_kda_head=64, d_head=128,
                 d_expert=1280, n_experts=320, top_k=8, n_shared=1,
                 gqa_layers=None, conv_taps=4, kda_gate_rank=128,
                 kda_neg_eigval=True, kda_chunk=64, route_scale=1.0,
                 rms_eps=1e-5, n_held=None, expert_offset=0, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.n_kda_head = n_kda_head
        self.n_shared = n_shared
        self.d_shared = d_expert * n_shared
        # published: a GQA layer first of every four
        self.gqa_layers = sorted(gqa_layers) if gqa_layers is not None \
            else list(range(0, n_layer, 4))
        self.conv_taps = conv_taps
        self.kda_gate_rank = kda_gate_rank
        self.kda_neg_eigval = kda_neg_eigval
        self.kda_chunk = kda_chunk
        self.route_scale = route_scale

    def mixer(self, idx):
        return "gqa" if idx in self.gqa_layers else "kda"


def build_solar_open2_pretrain(cfg: SolarOpen2Config, seq_len, is_test=False,
                               attn_impl="flash", fused_head=True,
                               checkpoints=None):
    """:func:`_causal_lm` over :class:`SolarOpen2Config`'s blocks; loss =
    its mean next-token CE and nothing else (the selection bias is held at
    zero, as in :func:`build_trinity_pretrain`).  ``checkpoints=[]``
    collects the block boundaries: the embedding's output and every
    block's, so that every block is computed again, the first too.  Returns
    its ``(feeds, parts, loss)``."""
    return _causal_lm(cfg, seq_len, attn_impl, is_test, fused_head,
                      checkpoints, checkpoint_input=True)[:3]


# -- Ling 3.0: KDA with a bounded gate beside latent attention, --------------
# -- group-limited routing, a leading dense layer ----------------------------

class LingConfig(DecoderConfig):
    """Ling-3.0-flash defaults (``inclusionAI/Ling-3.0-flash-VL``
    config.json, the language model; the vision tower is not built).  One
    Ling block, pre-norm, two norms, no bias anywhere: ``u = x +
    Mixer(RMS1(x))``, ``out = u + FFN(RMS2(u))``.  ``Mixer``: layer ``i``
    (published number) is latent attention where ``(i + 1) %
    layer_group_size == 0`` (``mla_layers``) and KDA otherwise: five KDA
    layers, then one MLA layer.  KDA (:func:`kda_attention`): both gates at
    full rank (``no_kda_lora``), the decay's gate bounded below by
    ``kda_lower_bound`` (``kda_safe_gate``), beta not doubled.  MLA
    (:func:`latent_attention`): Q at full rank (``q_lora_rank`` None),
    QK-norm on the content parts, a head-wise output gate.  ``FFN``:
    :func:`gated_ffn` of width ``d_inner`` in the first ``n_dense_layer``
    layers (``dense_layers``; the ``dense_ffn`` tag); else the shared
    expert (the same builder at ``d_shared``) plus ``moe_ffn`` over
    ``n_experts`` routed experts of width ``d_expert`` (``top_k`` a token
    among the ``topk_group`` best of ``n_group`` groups) with sigmoid
    scores, a selection bias held at zero, the kept scores renormalised
    (``+ 1e-20``) and scaled.

    ``first_layer``: the published number of this program's layer 0, where
    it holds a run of the layers (a pipeline stage); the dense layers and
    the MLA layers follow the published numbers.  ``n_head`` and
    ``n_kda_head`` are the heads HELD (default all 32), as
    :class:`SolarOpen2Config`."""

    rope_scaling = None
    kda_neg_eigval = False
    qk_norm = True
    head_gate = True
    tag_covers_add = ("dense",)

    def __init__(self, vocab_size=157184, d_model=2560, n_layer=42,
                 n_head=32, n_kda_head=32, d_head=128, kv_lora_rank=512,
                 d_nope=128, d_rope=64, d_v=128, d_inner=6144, d_expert=768,
                 d_shared=768, n_experts=512, top_k=8, n_group=8,
                 topk_group=4, n_dense_layer=2, layer_group_size=6,
                 first_layer=0, conv_taps=4, kda_lower_bound=-5.0,
                 kda_chunk=64, route_scale=2.5, rms_eps=1e-6,
                 rope_theta=6000000.0, n_held=None, expert_offset=0,
                 init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, d_head=d_head)
        self.n_kda_head = n_kda_head
        self.q_lora_rank = None
        self.kv_lora_rank = kv_lora_rank
        self.d_nope = d_nope
        self.d_rope = d_rope
        self.d_v = d_v
        self.d_inner = d_inner
        self.d_shared = d_shared
        self.n_group = self.n_route_group = n_group
        self.topk_group = topk_group
        self.layer_group_size = layer_group_size
        self.first_layer = first_layer
        numbers = range(first_layer, first_layer + n_layer)
        self.dense_layers = [j for j, i in enumerate(numbers)
                             if i < n_dense_layer]
        self.mla_layers = [j for j, i in enumerate(numbers)
                           if (i + 1) % layer_group_size == 0]
        self.conv_taps = conv_taps
        self.kda_gate_rank = None
        self.kda_lower_bound = kda_lower_bound
        self.kda_chunk = kda_chunk
        self.route_scale = route_scale
        self.rope_theta = rope_theta

    def mixer(self, idx):
        return "mla" if idx in self.mla_layers else "kda"

    def ffn(self, idx):
        return "dense" if idx in self.dense_layers else "routed"


def build_ling_pretrain(cfg: LingConfig, seq_len, fused_head=True,
                        checkpoints=None):
    """:func:`_causal_lm` over :class:`LingConfig`'s blocks; loss = its mean
    next-token CE and nothing else (the selection bias is held at zero and
    there is no auxiliary term).  ``checkpoints=[]`` collects the block
    boundaries: the embedding's output and every block's, KDA, MLA, dense
    or expert alike, as :func:`build_solar_open2_pretrain`.  Returns its
    ``(feeds, parts, loss)``."""
    return _causal_lm(cfg, seq_len, fused_head=fused_head,
                      checkpoints=checkpoints, checkpoint_input=True)[:3]


# -- Nemotron-H: Mamba-2 (SSD) mixers, position-free grouped-query attention --
# -- and un-gated ReLU^2 experts, ONE sublayer a block ------------------------

class NemotronHConfig(DecoderConfig):
    """NVIDIA-Nemotron-3-Nano-30B-A3B defaults (``nvidia/NVIDIA-Nemotron-3-
    Nano-30B-A3B-BF16`` config.json, ``model_type`` ``nemotron_h``; Mamba-2:
    arXiv:2405.21060, Nemotron-H: arXiv:2504.03624).  One nemotron_h block:
    ONE sublayer, pre-norm, no bias: ``out = x + Mixer(RMS(x))`` and no
    second half (the one norm is ``<prefix>.norm.w``).  ``pattern`` (the
    row's ``hybrid_override_pattern``) gives each block's sublayer by its
    letter: ``M`` a Mamba-2 mixer (:func:`mamba2_mixer`: ``n_mamba_head``
    heads of ``d_mamba_head``, ``n_group`` groups sharing ``B`` / ``C`` of
    ``d_state``, ``conv_taps`` taps with a bias, chunks of ``chunk``); ``*``
    causal grouped-query softmax attention with NO positional term, no gate
    and no QK-norm (``n_head`` over ``n_kv_head`` heads of ``d_head``, the
    ``attn`` tag); ``E`` the shared expert (:func:`relu2_ffn` at
    ``d_shared``, the ``shared_expert`` tag) plus ``moe_ffn`` over
    ``n_experts`` routed un-gated ReLU^2 experts of width ``d_expert``
    (``top_k`` a token) with sigmoid scores, a selection bias held at zero,
    the kept scores renormalised (``+ 1e-20``) and scaled."""

    gated = False
    act = "relu2"
    tag_covers_add = ("gqa",)

    def __init__(self, vocab_size=131072, d_model=2688,
                 pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                         "EMEMEMEME",
                 n_mamba_head=64, d_mamba_head=64, n_group=8, d_state=128,
                 conv_taps=4, chunk=128, n_head=32, n_kv_head=2, d_head=128,
                 d_expert=1856, d_shared=3712, n_experts=128, top_k=6,
                 route_scale=2.5, rms_eps=1e-5, n_held=None, expert_offset=0,
                 init_std=0.02):
        if set(pattern) - set("ME*"):
            raise ValueError(f"pattern {pattern!r}: M, E and * are built")
        super().__init__(vocab_size, d_model, len(pattern), n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.pattern = pattern
        self.n_mamba_head = n_mamba_head
        self.d_mamba_head = d_mamba_head
        self.n_group = n_group
        self.d_state = d_state
        self.conv_taps = conv_taps
        self.chunk = chunk
        self.d_shared = d_shared
        self.route_scale = route_scale

    def mixer(self, idx):
        return {"M": "mamba2", "*": "gqa", "E": None}[self.pattern[idx]]

    def ffn(self, idx):
        return "routed" if self.pattern[idx] == "E" else None


def build_nemotron_h_pretrain(cfg: NemotronHConfig, seq_len, fused_head=True,
                              checkpoints=None, attn_impl="flash"):
    """:func:`_causal_lm` over :class:`NemotronHConfig`'s
    ``len(cfg.pattern)`` one-sublayer blocks; loss = its mean next-token CE
    and nothing else (the selection bias is held at zero and there is no
    auxiliary term).  ``checkpoints=[]`` collects the block boundaries: the
    embedding's output and every block's, Mamba, attention or expert alike,
    as :func:`build_solar_open2_pretrain`.  Returns its ``(feeds, parts,
    loss)``."""
    return _causal_lm(cfg, seq_len, attn_impl, fused_head=fused_head,
                      checkpoints=checkpoints, checkpoint_input=True)[:3]


# -- the sequence mixers the configurations name ----------------------------

def yarn_softmax_factor(rope_scaling):
    """What YaRN multiplies the softmax scale by (the DeepSeek family's
    ``yarn_get_mscale(factor, mscale_all_dim)`` squared): ``(0.1
    mscale_all_dim ln(factor) + 1)^2``; 1 without a scaling group, without
    ``mscale_all_dim`` or at a factor of 1 or less."""
    import math
    if not rope_scaling or not rope_scaling.get("mscale_all_dim") \
            or rope_scaling["factor"] <= 1:
        return 1.0
    return (0.1 * rope_scaling["mscale_all_dim"]
            * math.log(rope_scaling["factor"]) + 1.0) ** 2


def latent_attention(x, cfg: JoyaiConfig, param_prefix="attn"):
    """Multi-head latent attention (MLA, arXiv:2405.04434 / 2412.19437
    §2.1.1) over ``x`` [b, t, d_model], causal, no bias, the training form
    (K and V expanded from the latent; no cache)::

        [c_q | c_kv | k_r] = x W_a          one fused [d, r_q + r_kv + d_rope]
        c_q = RMS(c_q), c_kv = RMS(c_kv)    norms on the two latents
        [q_nope | q_rope] = c_q W_qb        per head, d_nope | d_rope
        [k_nope | v]      = c_kv W_kvb      per head, d_nope | d_v
        q_rope, k_r = RoPE on adjacent pairs of the d_rope slice
        score = (q_nope k_nope^T + q_rope k_r^T) (d_nope + d_rope)^-1/2
        out = flash(q_nope, k_nope, v, q_rope, k_r) W_o

    ``k_r`` bypasses the latent and is ONE head that every query head reads.
    The flash op takes the pieces as the projections make them
    (``layers.flash_attention(q_rope=, k_rope=)``): the score is two
    products inside the kernels, the rotary key's ``[t, d_rope]`` read once
    for all heads through an index map and its gradient summed over them, so
    no ``d_nope + d_rope`` wide Q or K and no per-head copy of ``k_r`` is
    built in HBM (``tools/joyai_kernel_probe.py`` times both forms).
    The scores contract over ``d_nope + d_rope`` and the values are ``d_v``
    wide: the flash kernels' two widths.  With ``cfg.rope_scaling`` (YaRN)
    the rotary slice turns by ``layers.rope``'s frequency-table form and the
    softmax scale is multiplied by :func:`yarn_softmax_factor`.  Everything
    but the flash op lies under the ``mla_proj`` tag.  Parameters:
    ``<prefix>.a.w``,
    ``.q_norm.w``, ``.kv_norm.w``, ``.q_b.w``, ``.kv_b.w``, ``.out.w``.

    What a configuration may switch (each absent from :class:`JoyaiConfig`,
    whose program is then the one above): ``cfg.q_lora_rank`` None, Q at full
    rank, ``[q_nope | q_rope] = x W_q`` (``.q.w`` [d, h (d_nope + d_rope)];
    no Q latent, no ``q_norm``; ``W_a`` is [d, r_kv + d_rope]);
    ``cfg.qk_norm``: ``q_nope`` and ``k_nope`` RMS-normed over ``d_nope``,
    one learned [d_nope] scale for all query heads (``.q_nope_norm.w``) and
    one for all key heads (``.k_nope_norm.w``); the rotary slices are not
    normed (the rotary key is one head for all); ``cfg.head_gate``: the
    context of head ``i`` times ``sigmoid((x W_gate)_i)``, ``.gate.w`` [d,
    h], one gate a head and token, between the flash op and ``W_o``.
    ``cfg.n_head`` may be a SHARE of the layer's heads (tensor parallelism):
    ``W_o`` is [n_head d_v, d_model] and its result the partial sum over
    the heads held; the K/V latent and the rotary key are whole."""
    h, dn, dr, dv = cfg.n_head, cfg.d_nope, cfg.d_rope, cfg.d_v
    qk_norm = getattr(cfg, "qk_norm", False)

    def proj(v, size, name):
        return layers.fc(v, size=size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name=f"{param_prefix}.{name}.w"))

    def norm(v, name, axis=2):
        return _rms(v, cfg, f"{param_prefix}.{name}", axis)

    def heads(v, width):                      # [b, t, h * w] -> [b, h, t, w]
        return layers.transpose(
            layers.reshape(v, shape=[0, 0, h, width]), perm=[0, 2, 1, 3])

    def rotate(v):
        return layers.rope(v, dr, cfg.rope_theta, interleaved=True,
                           rope_scaling=cfg.rope_scaling)

    with name_scope("mla_proj"):
        if cfg.q_lora_rank is None:
            q = proj(x, h * (dn + dr), "q")
            c_kv, k_r = layers.split(proj(x, cfg.kv_lora_rank + dr, "a"),
                                     [cfg.kv_lora_rank, dr], dim=2)
        else:
            c_q, c_kv, k_r = layers.split(
                proj(x, cfg.q_lora_rank + cfg.kv_lora_rank + dr, "a"),
                [cfg.q_lora_rank, cfg.kv_lora_rank, dr], dim=2)
            q = proj(norm(c_q, "q_norm"), h * (dn + dr), "q_b")
        q_nope, q_rope = layers.split(heads(q, dn + dr), [dn, dr], dim=3)
        k_nope, v = layers.split(
            heads(proj(norm(c_kv, "kv_norm"), h * (dn + dv), "kv_b"),
                  dn + dv), [dn, dv], dim=3)
        if qk_norm:
            q_nope, k_nope = (norm(t, n, 3) for t, n in (
                (q_nope, "q_nope_norm"), (k_nope, "k_nope_norm")))
        k_r = rotate(layers.unsqueeze(k_r, [1]))            # [b, 1, t, dr]
        q_rope = rotate(q_rope)
    ctx = layers.flash_attention(
        q_nope, k_nope, v, causal=True, q_rope=q_rope, k_rope=k_r,
        sm_scale=float(dn + dr) ** -0.5
        * yarn_softmax_factor(cfg.rope_scaling))
    with name_scope("mla_proj"):
        ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])      # [b, t, h, dv]
        if getattr(cfg, "head_gate", False):
            ctx = ctx * layers.unsqueeze(
                layers.sigmoid(proj(x, h, "gate")), [3])
        ctx = layers.reshape(ctx, shape=[0, 0, h * dv])
        return proj(ctx, cfg.d_model, "out")


def short_conv_operator(x, d_model, taps=3, param_prefix="conv"):
    """``W_out (C * conv(B * u))`` with ``[B, C, u] = split3(W_in x)``: the
    input projection ``<prefix>.in_proj.w`` [d, 3 d], ``layers.short_conv``
    (filter ``<prefix>.filter`` [d, taps], drawn as a depthwise Conv1d's:
    uniform in ``+-taps^-0.5``) and the output projection
    ``<prefix>.out_proj.w`` [d, d]; no bias."""
    from ..initializer import UniformInitializer
    bcu = layers.fc(x, size=3 * d_model, num_flatten_dims=2, bias_attr=False,
                    param_attr=ParamAttr(name=f"{param_prefix}.in_proj.w"))
    bound = float(taps) ** -0.5
    y = layers.short_conv(bcu, taps, param_attr=ParamAttr(
        name=f"{param_prefix}.filter",
        initializer=UniformInitializer(-bound, bound)))
    return layers.fc(y, size=d_model, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=f"{param_prefix}.out_proj.w"))


def kda_attention(x, cfg: SolarOpen2Config, param_prefix="kda"):
    """One KDA sublayer over ``x`` [b, t, d_model] (the block's normed
    input), every op under the ``kda`` tag; ``h = cfg.n_kda_head`` heads of
    ``d = cfg.d_head``, no bias anywhere::

        [q | k | v | f | g | b] = x W_in      W_in [d_model, 3 h d + 2 r + h]
        q, k, v = silu(conv(q | k | v))       depthwise, causal, conv_taps
        decay g_t = -exp(A_log) softplus(f W_f + dt_bias);  beta = sigmoid(b)
        o = kda_scan(q, k, v, g_t, beta)      q, k normalised and q scaled
                                              inside; beta doubled where
                                              cfg.kda_neg_eigval
        y = W_o [rmsnorm_head(o) * sigmoid(g W_g)]

    ``<prefix>.in_proj.w`` holds the six input projections side by side (the
    two low-rank gates' down-projections, rank ``r = cfg.kda_gate_rank``, and
    beta's among them); ``<prefix>.conv.filter`` [3 h d, taps] the three
    filters (uniform in ``+-taps^-0.5``); ``<prefix>.f_up.w`` and
    ``<prefix>.g_up.w`` [r, h d]; ``<prefix>.A_log`` [h], ``<prefix>.dt_bias``
    [h d] (``layers.kda_gate``); ``<prefix>.o_norm.w`` [d] the norm over each
    head's output; ``<prefix>.out.w`` [h d, d_model], whose result is the
    partial sum over the heads held here.  The scan decides its lowering
    from what it is given: at heads of whole lane tiles (the published 128)
    on a TPU the kernel pair of ``pallas/kda.py``, whose backward starts
    from the chunk states the forward kept; ``kda_chunked``'s plain
    ``jax.numpy`` elsewhere (``paddle_tpu_kda_lowerings_total{impl}``).

    ``cfg.kda_gate_rank`` None: both gates at FULL rank (no ``f_up`` /
    ``g_up``; ``W_in`` is ``[d_model, 3 h d + 2 h d + h]`` and its ``f`` and
    ``g`` slices are the gates' inputs themselves).  ``cfg.kda_lower_bound``
    (absent or None: the form above): the decay's gate in its bounded form,
    ``g_t = lower_bound sigmoid(exp(A_log) (f + dt_bias))``
    (``layers.kda_gate(lower_bound=)``)."""
    from ..initializer import UniformInitializer
    h, d, r = cfg.n_kda_head, cfg.d_head, cfg.kda_gate_rank
    dq = h * d
    full = r is None
    r = dq if full else r

    def proj(v, size, name):
        return layers.fc(v, size=size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name=f"{param_prefix}.{name}.w"))

    with name_scope("kda"):
        qkv, f, g, b = layers.split(proj(x, 3 * dq + 2 * r + h, "in_proj"),
                                    [3 * dq, r, r, h], dim=2)
        bound = float(cfg.conv_taps) ** -0.5
        qkv = layers.short_conv(
            qkv, cfg.conv_taps, gated=False,
            param_attr=ParamAttr(name=f"{param_prefix}.conv.filter",
                                 initializer=UniformInitializer(-bound,
                                                                bound)))
        q, k, v = (layers.reshape(t, shape=[0, 0, h, d])
                   for t in layers.split(qkv, 3, dim=2))
        decay, beta = layers.kda_gate(
            f if full else proj(f, dq, "f_up"), b, h, param_prefix,
            lower_bound=getattr(cfg, "kda_lower_bound", None),
            rank="full" if full else r)
        o = layers.kda_scan(q, k, v, decay, beta, chunk=cfg.kda_chunk,
                            neg_eigval=cfg.kda_neg_eigval)
        # (the call above stands on lines 1339 and 1340, where the parent has
        # it: they are a source location inside the Mosaic bodies of
        # Solar-Open2's and Ling's lowered steps, which the compile cache
        # keys on: ROADMAP D13)
        o = _rms(o, cfg, f"{param_prefix}.o_norm", 3)
        y = layers.reshape(o, shape=[0, 0, dq]) \
            * layers.sigmoid(g if full else proj(g, dq, "g_up"))
        return proj(y, cfg.d_model, "out")


def mamba2_mixer(x, cfg: NemotronHConfig, param_prefix="mamba"):
    """One Mamba-2 sublayer over ``x`` [b, t, d_model] (the block's normed
    input), every op under the ``mamba`` tag; ``H = cfg.n_mamba_head`` heads
    of ``P = cfg.d_mamba_head`` (``d_inner = H P``), ``G = cfg.n_group``
    groups, ``N = cfg.d_state``; no bias on a projection::

        [z | xBC | dt] = x W_in           W_in [d_model, 2 H P + 2 G N + H]
        xBC = silu(conv(xBC) + b)         depthwise, causal, conv_taps
        [x | B | C] = xBC                 H P | G N | G N
        y = ssd_scan(x, dt, A_log, B, C, D, dt_bias)
                                          Delta = softplus(dt + dt_bias),
                                          decay exp(-Delta exp(A_log))
        out = W_out [w * rms_G(y * silu(z))]   the gate first, the RMS over
                                          each of the G groups of H P / G

    ``<prefix>.in_proj.w``; ``<prefix>.conv.filter`` [H P + 2 G N, taps]
    (uniform in ``+-taps^-0.5``) and ``<prefix>.conv.bias`` (zero);
    ``<prefix>.A_log`` = ``log(1 .. H)`` (``modeling_nemotron_h``'s
    ``arange``), ``<prefix>.D`` ones, ``<prefix>.dt_bias`` the inverse
    softplus of ``exp U(log 1e-3, log 1e-1)`` floored at 1e-4, drawn here
    from the parameter's name and not by the startup program's seed;
    ``<prefix>.norm.w`` [H P]; ``<prefix>.out.w`` [H P, d_model]."""
    import zlib
    from ..initializer import NumpyArrayInitializer, UniformInitializer
    h, p, g, n = (cfg.n_mamba_head, cfg.d_mamba_head, cfg.n_group,
                  cfg.d_state)
    d_in = h * p

    def proj(v, size, name):
        return layers.fc(v, size=size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name=f"{param_prefix}.{name}.w"))

    def per_head(name, values):
        return layers.create_parameter(
            [h], "float32", attr=ParamAttr(
                name=f"{param_prefix}.{name}",
                initializer=NumpyArrayInitializer(
                    np.asarray(values, np.float32))))

    rng = np.random.RandomState(zlib.crc32(param_prefix.encode()))
    dt0 = np.maximum(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), h)), 1e-4)
    with name_scope("mamba"):
        z, xbc, dt = layers.split(proj(x, 2 * d_in + 2 * g * n + h, "in_proj"),
                                  [d_in, d_in + 2 * g * n, h], dim=2)
        bound = float(cfg.conv_taps) ** -0.5
        xbc = layers.short_conv(
            xbc, cfg.conv_taps, gated=False,
            param_attr=ParamAttr(name=f"{param_prefix}.conv.filter",
                                 initializer=UniformInitializer(-bound,
                                                                bound)),
            bias_attr=ParamAttr(name=f"{param_prefix}.conv.bias"))
        xs, b, c = layers.split(xbc, [d_in, g * n, g * n], dim=2)
        y = layers.ssd_scan(
            layers.reshape(xs, shape=[0, 0, h, p]), dt,
            per_head("A_log", np.log(np.arange(1, h + 1))),
            layers.reshape(b, shape=[0, 0, g, n]),
            layers.reshape(c, shape=[0, 0, g, n]),
            per_head("D", np.ones(h)),
            per_head("dt_bias", dt0 + np.log(-np.expm1(-dt0))),
            chunk=cfg.chunk)
        y = layers.gated_rms_norm(
            layers.reshape(y, shape=[0, 0, d_in]), z, groups=g,
            epsilon=cfg.rms_eps,
            param_attr=ParamAttr(name=f"{param_prefix}.norm.w"))
        return proj(y, cfg.d_model, "out")


# -- the assembled block and the loop over it ---------------------------------

#: mixer kind -> (its builder over the block's normed input, the
#: configuration and a parameter prefix; the suffix its parameters take).
#: These tag themselves; ``"gqa"`` is :func:`grouped_query_attention`.
MIXERS = {
    "mla": (latent_attention, "attn"),
    "kda": (kda_attention, "kda"),
    "mamba2": (mamba2_mixer, "mamba"),
    "conv": (lambda n, cfg, prefix: short_conv_operator(
        n, cfg.d_model, cfg.conv_taps, prefix), "conv"),
}


def _rms(x, cfg, name, axis=2):
    """RMS norm of ``x`` from ``axis`` on, its weight ``<name>.w``."""
    return layers.rms_norm(x, begin_norm_axis=axis, epsilon=cfg.rms_eps,
                           param_attr=ParamAttr(name=f"{name}.w"))


def _rotary_step(cfg, d_head):
    """The hook's rotary step ``(t, name) -> t`` for ``cfg``: ``layers.rope``
    at ``cfg.rope_theta`` over the sequence axis, or, where the stream is
    two copies of one sequence (``cfg.block_diffusion``), over each copy
    apart (:func:`_turned_by_copy`)."""
    def turn(t, name=None):
        return layers.rope(t, d_head, cfg.rope_theta)
    if cfg.block_diffusion:
        return lambda t, name: _turned_by_copy(t, turn)
    return turn


def _turned_by_copy(t, turn):
    """``turn`` (the rotary embedding, whose position is the index along
    the sequence axis) over a stream of two copies of one sequence, each
    copy from position 0: the copies are folded into the heads (4-D ``[b,
    heads, 2L, d_head]``) or the batch (3-D ``[b, 2L, d]``) and back, two
    reshapes that move nothing, and the op and its kernel are what they
    are for one copy."""
    shape = [int(d) for d in t.shape]
    if len(shape) == 4:
        folded = [0, 2 * shape[1], shape[2] // 2, shape[3]]
        back = [0, shape[1], shape[2], shape[3]]
    else:
        folded, back = [-1, shape[1] // 2, shape[2]], [-1] + shape[1:]
    with name_scope("bd_stream"):
        t = layers.reshape(t, shape=folded)
    t = turn(t)
    with name_scope("bd_stream"):
        return layers.reshape(t, shape=back)


def grouped_query_attention(n, cfg, idx, param_prefix, attn_impl="flash",
                            is_test=False):
    """Causal self-attention over ``n`` (under ``cfg.block_diffusion``: block
    diffusion's mask over the two copies ``n`` holds, each turned by its own
    positions) as ``cfg`` describes layer ``idx``
    (:class:`DecoderConfig`): :func:`multi_head_attention` with no bias at
    ``n_head`` over ``n_kv_head`` heads, its hook the layer's QK-norm
    (``<prefix>.q_norm.w``, ``.k_norm.w``) and rotary, before the head
    split where the norm is over the projection, after it otherwise."""
    d_head = cfg.d_head or cfg.d_model // cfg.n_head
    steps = []
    if cfg.qk_norm_over:
        axis = 2 if cfg.qk_norm_over == "projection" else 3
        steps.append(lambda t, name: _rms(
            t, cfg, f"{param_prefix}.{name}_norm", axis))
    if cfg.rotary(idx):
        steps.append(_rotary_step(cfg, d_head))

    def hook(q, k):
        t = {"q": q, "k": k}
        order = [(name, step) for name in t for step in steps] \
            if cfg.qk_in_turn else \
            [(name, step) for step in steps for name in t]
        for name, step in order:
            t[name] = step(t[name], name)
        return t["q"], t["k"]

    where = "qk_hook" if cfg.qk_norm_over == "projection" else "head_hook"
    return multi_head_attention(
        n, n, n, cfg.d_model, cfg.n_head, is_test=is_test,
        param_prefix=param_prefix, attn_impl=attn_impl,
        causal=not cfg.block_diffusion,
        block_diffusion=cfg.block_diffusion, bias=False, n_kv_head=cfg.n_kv_head, d_head=cfg.d_head,
        window=cfg.window_at(idx), out_gate=cfg.out_gate,
        **({where: hook} if steps else {}))


def routed_ffn(x, cfg, param_prefix, router_x=None):
    """The routed FFN over ``x`` (the block's normed stream) as ``cfg``
    describes it (:class:`DecoderConfig`): the shared expert where there is
    one (``<prefix>.shared.*``, under the ``shared_expert`` tag; its two
    parameters are created before the router's), then ``layers.moe_ffn``
    (``<prefix>.moe.*``).  Returns the terms to add to the stream, in their
    order, and ``moe_ffn``'s ``(lb_loss, z_loss, expert_load)``."""
    dense = gated_ffn if cfg.gated else relu2_ffn
    terms = []
    if cfg.d_shared:
        with name_scope("shared_expert"):
            terms.append(dense(x, cfg.d_shared, cfg.d_model,
                               f"{param_prefix}.shared"))
    moe, *aux = layers.moe_ffn(
        x, cfg.n_experts, cfg.top_k, cfg.d_expert,
        norm_topk_prob=cfg.route_norm, param_prefix=f"{param_prefix}.moe",
        initializer=initializer.NormalInitializer(0.0, cfg.init_std),
        score_func=cfg.score_func, select_bias=cfg.select_bias,
        norm_eps=cfg.route_norm_eps, route_scale=cfg.route_scale,
        num_held=cfg.n_held, expert_offset=cfg.expert_offset, act=cfg.act,
        router_x=router_x, n_group=cfg.n_route_group,
        topk_group=cfg.topk_group, gated=cfg.gated)
    return terms + [moe], tuple(aux)


def decoder_block(x, cfg, idx=0, attn_impl="flash", is_test=False,
                  param_prefix=None, residual=plain_residual):
    """Layer ``idx`` of the decoder ``cfg`` describes
    (:class:`DecoderConfig`): the sublayers ``cfg.mixer(idx)`` and
    ``cfg.ffn(idx)`` name, each over an RMS norm of the stream and put back
    into it by ``residual(x, sublayer, name)``: :func:`plain_residual` (the
    ``+``) by default; :func:`hyper_connection` makes the rule of a widened
    stream, whose parameters are ``<prefix>.hc_attn.*`` and
    ``<prefix>.hc_ffn.*``.  No bias anywhere.  ``param_prefix`` (default
    ``dec_<idx>``) names the parameters: the mixer's under its suffix of
    ``MIXERS`` (``.attn`` for ``"gqa"``), a dense FFN's ``.ffn.*``, a
    routed one's as :func:`routed_ffn` says, and the norms ``.ln1``,
    ``.ln2`` ... in the order they are made (``.norm`` in a block of one
    sublayer).  Returns ``(out, routed)``: ``routed`` is ``moe_ffn``'s
    ``(lb_loss, z_loss, expert_load)``, None in a block with no routed
    FFN."""
    p = param_prefix or f"dec_{idx}"
    mixer, ffn = cfg.mixer(idx), cfg.ffn(idx)
    names = iter(["norm"] if None in (mixer, ffn)
                 else ["ln1", "ln2", "ln3", "ln4"])
    seen = {}

    def norm(v):
        return _rms(v, cfg, f"{p}.{next(names)}")

    def mix(n):
        seen["mixer_in"] = n
        if mixer == "gqa":
            return [grouped_query_attention(n, cfg, idx, f"{p}.attn",
                                            attn_impl, is_test)]
        build, suffix = MIXERS[mixer]
        return [build(n, cfg, f"{p}.{suffix}")]

    def feed_forward(m):
        if ffn == "dense":
            dense = gated_ffn if cfg.gated else relu2_ffn
            return [dense(m, cfg.d_inner, cfg.d_model, f"{p}.ffn")]
        terms, seen["routed"] = routed_ffn(
            m, cfg, p, seen["mixer_in"] if cfg.router_before_mixer else None)
        return terms

    def sublayer(x, part, tag, build, name):
        """``build`` over a norm of the stream, under ``tag``, its terms put
        back by the rule.  The tag is left with the part or, where the
        configuration has it cover the residual add, behind the rule."""
        with contextlib.ExitStack() as held:
            def normed(u):
                n = norm(u)
                with contextlib.ExitStack() as now:
                    if tag:
                        (held if part in cfg.tag_covers_add
                         else now).enter_context(name_scope(tag))
                    terms = build(n)
                return [norm(sum(terms[1:], terms[0]))] \
                    if cfg.sandwich_norm else terms
            return residual(x, normed, f"{p}.{name}")

    if mixer is not None:
        x = sublayer(x, mixer, cfg.mixer_tags.get(mixer), mix, "hc_attn")
    if ffn is not None:
        x = sublayer(x, ffn, "dense_ffn" if ffn == "dense" else None,
                     feed_forward, "hc_ffn")
    return x, seen.get("routed")


def _next_token_feeds(cfg, seq_len, embed):
    """The causal LM's feeds and stream: ``src_ids`` through the table, and
    ``lm_label`` as the pipeline shifted it."""
    src_ids = layers.data("src_ids", shape=[seq_len], dtype="int64")
    lm_label = layers.data("lm_label", shape=[seq_len], dtype="int64")
    return (src_ids, lm_label), embed(src_ids)


def _next_token_loss(x, feeds, cfg, fused_head, table):
    """Mean next-token CE of the final norm's output ``x`` (label 0
    excluded, as in the other builders)."""
    return _lm_head_loss(x, cfg, feeds[1], fused_head, "lm_out", bias=False,
                         table=table)[1]


def _causal_lm(cfg, seq_len, attn_impl="flash", is_test=False,
               fused_head=True, checkpoints=None, checkpoint_input=False,
               residual=plain_residual, enter=None, leave=None,
               feeds_of=_next_token_feeds, loss_of=_next_token_loss):
    """The causal LM every decoder here is: ids -> embedding (no position
    table; times ``sqrt(d_model)`` under ``cfg.mup``) -> ``cfg.n_layer``
    :func:`decoder_block` -> final RMSNorm -> bias-free head, untied
    (``lm_out.w``) or, under ``cfg.tie_embeddings``, over the embedding
    table itself (``word_embedding`` is read by the lookup and by the head,
    and its gradient is the sum of the two).  Loss = mean next-token CE
    (``lm_label`` as the pipeline shifted it; label 0 excluded, as in the
    other builders).  The models have no dropout, so ``is_test`` only
    reaches the attention's choice of path.  ``enter`` / ``leave`` map the
    embedding to the stream the blocks carry and back (a list of variables
    under :func:`hyper_connection`).  Another objective over the same loop
    gives the two things that differ: ``feeds_of(cfg, seq_len, embed)``, the
    feeds and the stream made of them (``embed``: ids through the table),
    and ``loss_of(x, feeds, cfg, fused_head, table)``, the loss of the final
    norm's output (:func:`build_sdar_pretrain`: two id feeds, a doubled
    stream, ``leave`` taking its noisy half, a weighted denoising loss).

    ``checkpoints=[]`` collects the block outputs (every stream of a
    widened one) for ``RecomputeOptimizer``, and with ``checkpoint_input``
    the first block's input too: what lies before the first checkpoint is
    no segment and would be kept whole (0.95 GB and, XLA then
    rematerialising on its own, 17 ms a step at Solar-Open2's published
    widths: benchmark/traffic/lm_s8192_r64.json, recompute_why).  Each
    entry point passes the rule its accepted cell's step was measured
    under; one rule for all is a pair on the chip away (ROADMAP D19).

    Returns ``(feeds, parts, loss, aux)``: ``parts`` = {"expert_load": [per
    routed layer], "hidden": the final norm's output}, ``aux`` the routed
    layers' ``(lb_loss, z_loss)`` for the recipe that trains on them."""
    def embed(ids):
        x = layers.embedding(ids, size=[cfg.vocab_size, cfg.d_model],
                             param_attr=ParamAttr(name="word_embedding"))
        return layers.scale(x, scale=float(cfg.d_model) ** 0.5) \
            if cfg.mup else x

    feeds, x = feeds_of(cfg, seq_len, embed)
    if enter is not None:
        x = enter(x)

    def keep(x):
        if checkpoints is not None:
            checkpoints.extend(x if isinstance(x, list) else [x])

    if checkpoint_input:
        keep(x)
    routed = []
    for i in range(cfg.n_layer):
        x, r = decoder_block(x, cfg, i, attn_impl, is_test,
                             residual=residual)
        if r is not None:
            routed.append(r)
        keep(x)
    if leave is not None:
        x = leave(x)
    x = _rms(x, cfg, "final_norm")
    table = default_main_program().global_block().var("word_embedding") \
        if cfg.tie_embeddings else None
    loss = loss_of(x, feeds, cfg, fused_head, table)
    parts = {"expert_load": [r[2] for r in routed], "hidden": x}
    return feeds, parts, loss, [r[:2] for r in routed]


class SdarConfig(DecoderConfig):
    """SDAR-30B-A3B-Chat defaults (``JetLM/SDAR-30B-A3B-Chat`` config.json,
    ``model_type`` ``sdar_moe``; arXiv:2510.06303; the objective is block
    diffusion, arXiv:2503.09573).  The network is Qwen3-MoE's, which
    ``sdar_moe`` keeps: ``h = x + Attn(RMS(x))``, ``out = h + MoE(RMS(h))``,
    no bias, no gate, no shared expert and no dense layer; ``n_head`` over
    ``n_kv_head`` heads of ``d_head`` with a per-head RMS norm on Q and K
    and rotary behind it; softmax scores over ``n_experts``, the ``top_k``
    kept renormalised, no selection bias, SiLU-gated experts of
    ``d_expert``; untied tables.  What is its own is the objective:
    ``block_diffusion``, the block length B of the mask every layer runs
    over the doubled stream, and ``mask_token_id``, what a noised token
    becomes (the feeds bring the noisy ids; :func:`build_sdar_pretrain`)."""

    qk_norm_over = "head"
    score_func = "softmax"
    select_bias = False
    route_norm = True
    route_norm_eps = 0.0

    def __init__(self, vocab_size=151936, d_model=2048, n_layer=48,
                 n_head=32, n_kv_head=4, d_head=128, d_expert=768,
                 n_experts=128, top_k=8, rms_eps=1e-6, rope_theta=1e6,
                 block_diffusion=4, mask_token_id=151669, n_held=None,
                 expert_offset=0, init_std=0.02):
        super().__init__(vocab_size, d_model, n_layer, n_head, d_expert,
                         n_experts, top_k, rms_eps, n_held, expert_offset,
                         init_std, n_kv_head, d_head)
        self.rope_theta = rope_theta
        self.block_diffusion = block_diffusion
        self.mask_token_id = mask_token_id


def build_sdar_pretrain(cfg: SdarConfig, seq_len, fused_head=True,
                        checkpoints=None, attn_impl="flash"):
    """Block-diffusion training of :class:`SdarConfig`'s decoder: the loop
    of :func:`_causal_lm` over another objective.  Feeds, each ``[b,
    seq_len]``: ``clean_ids`` (x_0), ``noisy_ids`` (x_t: x_0 with each
    token of block ``b`` replaced by ``cfg.mask_token_id`` with probability
    ``t_b``), ``lm_label`` (x_0 where x_t is the mask token, 0 elsewhere:
    label 0 is excluded, as in the other builders) and ``loss_weight``
    (float32, ``1 / t_b`` of each position's block).  The stream is ``[E(x_t);
    E(x_0)]``, ``2 · seq_len`` rows through one table, positions ``i mod
    seq_len``, every layer under the block-diffusion mask
    (``layers.flash_attention(block_diffusion=cfg.block_diffusion)``); the
    final norm and the head read the noisy half alone, and the loss is ``(1
    / (b · seq_len)) Σ_{masked i} w_i · CE(logits_i, x_0^i)``: the token AT
    its position, no shift, no auxiliary term.  What the objective adds
    outside attention (joining the copies, the positions' restart, taking
    the noisy half, weighting the loss) lies under the ``bd_stream`` tag.
    ``checkpoints=[]`` collects the block boundaries, the stream's first
    among them.  Returns ``(feeds, parts, loss)``; ``parts["hidden"]`` is
    the final norm's output over the noisy half."""
    if seq_len % cfg.block_diffusion:
        raise ValueError(f"seq_len {seq_len} is not whole blocks of "
                         f"{cfg.block_diffusion}")

    def feeds_of(cfg, seq_len, embed):
        ids = [layers.data(n, shape=[seq_len], dtype="int64")
               for n in ("clean_ids", "noisy_ids", "lm_label")]
        weight = layers.data("loss_weight", shape=[seq_len],
                             dtype="float32")
        noisy, clean = embed(ids[1]), embed(ids[0])
        with name_scope("bd_stream"):
            return (*ids, weight), layers.concat([noisy, clean], axis=1)

    def noisy_half(x):
        with name_scope("bd_stream"):
            return layers.slice(x, axes=[1], starts=[0], ends=[seq_len])

    def loss_of(x, feeds, cfg, fused_head, table):
        label, weight = feeds[2], feeds[3]
        w_attr = ParamAttr(name="lm_out.w")
        if fused_head:
            ce = layers.fused_lm_head_ce(x, cfg.vocab_size, label,
                                         param_attr=w_attr, bias_attr=False,
                                         ignore_index=0)
        else:
            logits = layers.fc(x, size=cfg.vocab_size, num_flatten_dims=2,
                               param_attr=w_attr, bias_attr=False)
            ce = layers.softmax_with_cross_entropy(
                logits, layers.unsqueeze(label, [2]), ignore_index=0)
        with name_scope("bd_stream"):
            w = weight * layers.cast(label > 0, "float32")
            return layers.reduce_mean(ce * layers.unsqueeze(w, [2]))

    return _causal_lm(cfg, seq_len, attn_impl, fused_head=fused_head,
                      checkpoints=checkpoints, checkpoint_input=True,
                      leave=noisy_half, feeds_of=feeds_of,
                      loss_of=loss_of)[:3]


def annotate_tensor_parallel(program=None):
    """Megatron-style TP layout via dist_spec (SURVEY §2.5: TP is a
    capability the reference LACKS — first-class here)."""
    program = program or default_main_program()
    for p in program.all_parameters():
        n = p.name
        if n.endswith((".q.w", ".k.w", ".v.w", ".qkv.w", ".fc1.w")):
            p.dist_spec = (None, "mp")          # column parallel
        elif n.endswith((".q.b", ".k.b", ".v.b", ".qkv.b", ".fc1.b")):
            p.dist_spec = ("mp",)
        elif n.endswith((".out.w", ".fc2.w")):
            p.dist_spec = ("mp", None)          # row parallel
        elif n == "word_embedding":
            p.dist_spec = ("mp", None)          # vocab sharded
        elif n == "mlm_out.w":
            p.dist_spec = (None, "mp")
        elif n == "mlm_out.b":
            p.dist_spec = ("mp",)
    return program


# -- Transformer-base NMT (BASELINE config #3, WMT14 en-de) ------------------

def build_transformer_nmt(src_vocab, trg_vocab, seq_len, d_model=512,
                          n_layer=6, n_head=8, d_inner=2048, dropout=0.1,
                          is_test=False, fused_head=False):
    """Encoder-decoder NMT Transformer (ref dist_transformer.py transformer()).

    Decoder self-attention uses a causal additive bias; cross-attention
    attends encoder output.  ``fused_head=True`` computes projection+CE
    with the chunked ``fused_lm_head_ce`` op (the [tokens, 37k] logits
    never hit HBM); ``logits`` is returned as None in that mode."""
    src_ids = layers.data("src_ids", shape=[seq_len], dtype="int64")
    src_pos = layers.data("src_pos", shape=[seq_len], dtype="int64")
    trg_ids = layers.data("trg_ids", shape=[seq_len], dtype="int64")
    trg_pos = layers.data("trg_pos", shape=[seq_len], dtype="int64")
    label = layers.data("label", shape=[seq_len], dtype="int64")

    enc_out = encoder(src_ids, src_pos, src_vocab, seq_len + 1, n_layer,
                      d_model, d_inner, n_head, dropout, is_test=is_test)

    # causal bias [1, 1, t, t]
    causal = np.triu(np.full((seq_len, seq_len), -1e9, np.float32), k=1)
    causal_var = layers.assign(causal.reshape(1, 1, seq_len, seq_len))
    causal_var.stop_gradient = True

    x = layers.embedding(trg_ids, size=[trg_vocab, d_model],
                         param_attr=ParamAttr(name="trg_word_embedding"))
    pos = layers.embedding(trg_pos, size=[seq_len + 1, d_model],
                           param_attr=ParamAttr(name="trg_pos_embedding"))
    x = x + pos
    x = layers.layer_norm(x, begin_norm_axis=2)
    for i in range(n_layer):
        attn = multi_head_attention(x, x, x, d_model, n_head, dropout,
                                    attn_bias=causal_var, is_test=is_test,
                                    param_prefix=f"dec_{i}.self")
        x = layers.layer_norm(x + attn, begin_norm_axis=2)
        cross = multi_head_attention(x, enc_out, enc_out, d_model, n_head,
                                     dropout, is_test=is_test,
                                     param_prefix=f"dec_{i}.cross")
        x = layers.layer_norm(x + cross, begin_norm_axis=2)
        ffn = positionwise_ffn(x, d_inner, d_model, dropout, is_test,
                               param_prefix=f"dec_{i}.ffn", act="relu")
        x = layers.layer_norm(x + ffn, begin_norm_axis=2)

    if fused_head:
        loss = layers.fused_lm_head_ce(
            x, trg_vocab, label, bias_attr=False,
            param_attr=ParamAttr(name="nmt_out.w"), ignore_index=0)
        mask = layers.cast(label > 0, "float32")
        avg_loss = layers.reduce_sum(loss * layers.unsqueeze(mask, [2])) / \
            (layers.reduce_sum(mask) + 1e-6)
        return (src_ids, src_pos, trg_ids, trg_pos, label), None, avg_loss
    logits = layers.fc(x, size=trg_vocab, num_flatten_dims=2,
                       param_attr=ParamAttr(name="nmt_out.w"),
                       bias_attr=False)
    loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(label, [2]), ignore_index=0)
    mask = layers.cast(label > 0, "float32")
    avg_loss = layers.reduce_sum(loss * layers.unsqueeze(mask, [2])) / \
        (layers.reduce_sum(mask) + 1e-6)
    return (src_ids, src_pos, trg_ids, trg_pos, label), logits, avg_loss
