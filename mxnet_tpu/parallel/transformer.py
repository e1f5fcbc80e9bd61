"""Multi-axis-parallel transformer training step: dp x tp x sp x ep.

Beyond the reference (which stops at data parallelism + group2ctx operator
placement, SURVEY.md §2.3): this is the TPU-native scaling recipe — pick a
``jax.sharding.Mesh``, annotate parameter/activation shardings with
``NamedSharding``, and let XLA insert the collectives:

- ``dp``  batch-sharded activations, gradient all-reduce;
- ``tp``  attention heads + FFN hidden sharded (Megatron-style splits,
          all-reduce on the row-parallel projections);
- ``sp``  sequence sharded with :mod:`ring_attention`'s ppermute ring;
- ``ep``  MoE expert weights sharded, token-expert mixing einsums become
          all-to-all-style collectives.

One ``jit`` compiles the whole step (fwd + bwd + optimizer); the class is
the flagship long-context/distributed path the driver's
``dryrun_multichip`` validates on a virtual mesh.
"""
from __future__ import annotations

import numpy as np

from ..observability import device_scope, trace_span
from . import lm_layers
from .ring_attention import ring_attention

__all__ = ["TransformerParallel"]


class TransformerParallel:
    """A compact causal-LM transformer with explicit mesh shardings.

    Parameters are a flat dict of jax arrays placed with NamedShardings;
    ``step`` runs fwd+bwd+SGD as one compiled program over the mesh.

    ``layers`` describes the model layer by layer, each an (attention
    kind, FFN kind) pair (docs/lm_layers.md). Without it the model is
    ``n_layers`` of the first block, ``("mha", "soft_moe")``: multi-head
    attention without positions, a weightless RMSNorm and a GELU FFN whose
    experts all run under a softmax gate. The other kinds — ``"mla"``
    (latent attention with rotary positions), ``"gqa"`` (grouped-query
    attention: each layer its own head count, window and rotary tables
    under ``arch["gqa"]["layers"]``, a sigmoid gate a head), ``"ssm"`` (a
    state-space mixer), ``"gmu"`` and ``"diff"`` (a gated memory unit and
    differential attention, which may read the scan output or the k and v
    that an earlier layer made: ``arch["shared"]`` names the makers),
    ``"swiglu"`` and ``"moe"`` (sigmoid top-k routing over
    ``arch["moe"]["n_experts"]`` experts of which this rank holds the
    range ``experts_held``, plus a shared expert) — take their widths
    from ``arch``, have learned norm weights and a learned final norm, and
    train on dp meshes; with ``arch["tied_head"]`` the head is the
    embedding transposed. ``remat`` recomputes each layer in the backward
    pass, but for what it keeps by name
    (``lm_layers.KEPT_BY_A_RECOMPUTED_LAYER``: the flash output among it);
    a shared value is its maker's output and its readers' input, so no
    reader makes it again.
    """

    def __init__(self, mesh, vocab=64, d_model=32, n_heads=4, n_layers=2,
                 d_ff=64, n_experts=2, dtype=np.float32, layers=None,
                 arch=None, remat=False):
        self.mesh = mesh
        self.layers = (tuple(tuple(k) for k in layers) if layers is not None
                       else (("mha", "soft_moe"),) * n_layers)
        self.cfg = dict(vocab=vocab, d_model=d_model, n_heads=n_heads,
                        n_layers=len(self.layers), d_ff=d_ff,
                        n_experts=n_experts)
        self.arch = dict(arch or {})
        self.remat = bool(remat)
        self.dtype = dtype
        self.axes = set(mesh.axis_names)
        for attn, ffn in self.layers:
            if (attn not in lm_layers.ATTENTION_KINDS
                    or ffn not in lm_layers.FFN_KINDS):
                raise ValueError("unknown layer kinds %r" % ((attn, ffn),))
        #: the first block throughout: its flat table, no learned norm
        self.classic = all(k == ("mha", "soft_moe") for k in self.layers)
        if not self.classic and any(
                mesh.shape[a] > 1 for a in self.axes - {"dp"}):
            raise NotImplementedError(
                "layer kinds beside ('mha', 'soft_moe') train on dp meshes; "
                "got axes %s" % dict(mesh.shape))
        self._step_jit = None   # ONE compiled step; lr is a traced arg
        self._step_cache = {}   # lr -> binding wrapper (identity-stable)
        self._step_calls = 0    # numbers the transformer.step spans
        self._stats_jit = None  # routing_stats' forward

    @classmethod
    def from_config(cls, mesh, cfg, dtype=np.float32, remat=False):
        """A model from a published ``config.json``'s keys: MLA layers
        (the DeepSeek-MLA family's names, ``model_type: sarvam_mla``
        among them), the first ``first_k_dense_replace`` with a SwiGLU FFN
        and the rest routed; under ``model_type: laguna``, the model its
        layer lists describe (:func:`_laguna`); under ``model_type:
        phi4flash``, state-space, differential-attention and gated-memory
        layers by published index (:func:`_phi4flash`). ``num_experts`` counts
        the experts HELD; ``cfg["published"]["num_experts"]`` (the
        router's width) and ``cfg["deployment"]["experts_held"]`` (their
        range) say of which share, and default to all of them."""
        if cfg.get("model_type") == "phi4flash":
            layers, arch = _phi4flash(cfg)
            return cls(mesh, vocab=cfg["vocab_size"],
                       d_model=cfg["hidden_size"],
                       n_heads=cfg["num_attention_heads"],
                       d_ff=cfg["intermediate_size"], dtype=dtype,
                       layers=layers, arch=arch, remat=remat)
        held = cfg.get("deployment", {}).get(
            "experts_held", (0, cfg["num_experts"]))
        moe = {"n_experts": cfg.get("published", {}).get(
                   "num_experts", cfg["num_experts"]),
               "top_k": cfg["num_experts_per_tok"],
               "d_expert": cfg["moe_intermediate_size"],
               "experts_held": (int(held[0]), int(held[1]))}
        if cfg.get("model_type") == "laguna":
            layers, arch = _laguna(cfg, moe)
            return cls(mesh, vocab=cfg["vocab_size"],
                       d_model=cfg["hidden_size"],
                       n_heads=cfg["num_attention_heads"],
                       d_ff=cfg["intermediate_size"], dtype=dtype,
                       layers=layers, arch=arch, remat=remat)
        n_dense = cfg.get("first_k_dense_replace", 0)
        layers = [("mla", "swiglu" if li < n_dense else "moe")
                  for li in range(cfg["num_hidden_layers"])]
        moe.update(scale=cfg["routed_scaling_factor"],
                   n_shared=cfg["num_shared_experts"])
        arch = {k: cfg[k] for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "rms_norm_eps")}
        arch["rope"] = dict(cfg.get("rope_scaling") or {},
                            theta=cfg["rope_theta"])
        arch["moe"] = moe
        return cls(mesh, vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
                   n_heads=cfg["num_attention_heads"],
                   d_ff=cfg["intermediate_size"], dtype=dtype, layers=layers,
                   arch=arch, remat=remat)

    # --- sharding helpers -------------------------------------------------
    def _ns(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec

        spec = tuple(s if s in self.axes else None for s in spec)
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def param_table(self):
        """name -> (shape, init) of every leaf, in the order keys are
        folded in: ``("normal", std)`` or a constant."""
        c = self.cfg
        d, f, e = c["d_model"], c["d_ff"], c["n_experts"]
        std = ("normal", 0.02)
        table = {"embed": ((c["vocab"], d), std)}
        if not self.arch.get("tied_head"):
            table["out_w"] = ((d, c["vocab"]), std)
        if not self.classic:
            table.update(lm_layers.norm_leaves("final_norm", d, self.arch))
        for li, kinds in enumerate(self.layers):
            p = "l%d_" % li
            if kinds[0] == "mha":
                for name in ("wq", "wk", "wv", "wo"):
                    table[p + name] = ((d, d), std)
            if kinds[1] == "soft_moe":
                table[p + "w1"] = ((e, d, f), std)
                table[p + "w2"] = ((e, f, d), std)
                table[p + "gate"] = ((d, e), std)
            table.update(lm_layers.layer_table(li, kinds, c, self.arch))
        return table

    def param_shardings(self):
        # the first block: column-parallel QKV (heads on tp), row-parallel
        # proj; experts on ep, hidden dim on tp (Megatron FFN split). The
        # other kinds' leaves are replicated (dp meshes only)
        sh = {name: self._ns(*(None,) * len(shape))
              for name, (shape, _) in self.param_table().items()}
        for li, (attn, ffn) in enumerate(self.layers):
            p = "l%d_" % li
            if attn == "mha":
                sh[p + "wq"] = self._ns(None, "tp")
                sh[p + "wk"] = self._ns(None, "tp")
                sh[p + "wv"] = self._ns(None, "tp")
                sh[p + "wo"] = self._ns("tp", None)
            if ffn == "soft_moe":
                sh[p + "w1"] = self._ns("ep", None, "tp")
                sh[p + "w2"] = self._ns("ep", "tp", None)
                sh[p + "gate"] = self._ns(None, "ep")
        return sh

    def init(self, seed=0):
        """Every leaf made on the device from a key (one jitted program;
        nothing is drawn on the host), placed by ``param_shardings``."""
        import jax
        import jax.numpy as jnp

        table = self.param_table()
        dtype = jnp.dtype(self.dtype)

        def make(key):
            out = {}
            for i, (name, (shape, init)) in enumerate(table.items()):
                if isinstance(init, tuple):
                    leaf = jnp.float32(init[1]) * jax.random.normal(
                        jax.random.fold_in(key, i), shape, jnp.float32)
                else:
                    leaf = jnp.full(shape, init, jnp.float32)
                out[name] = leaf.astype(dtype)
            return out

        return jax.jit(make, out_shardings=self.param_shardings())(
            jax.random.PRNGKey(seed))

    # --- the model --------------------------------------------------------
    def _qkv(self, params, p, ln):
        """Q/K/V projections of a normed activation block, returned in
        the (B, T, H, hd) storage layout the paged KV cache uses."""
        c = self.cfg
        B, T = ln.shape[0], ln.shape[1]
        H = c["n_heads"]
        hd = c["d_model"] // H
        q = (ln @ params[p + "wq"]).reshape(B, T, H, hd)
        k = (ln @ params[p + "wk"]).reshape(B, T, H, hd)
        v = (ln @ params[p + "wv"]).reshape(B, T, H, hd)
        return q, k, v

    def _moe_ffn(self, params, p, x):
        """MoE FFN residual delta: soft gate over ep-sharded experts.
        Shared verbatim by the training forward, the prefill forward and
        the single-token decode step, so the three paths cannot drift."""
        import jax
        import jax.numpy as jnp

        ln = _rms_norm(x)
        gate = jax.nn.softmax(ln @ params[p + "gate"], axis=-1)
        # (B,T,d) x (E,d,f) -> (B,T,E,f): expert compute stays on the
        # ep shards; the gate-weighted combine is the all-to-all mix
        hidden = jnp.einsum("btd,edf->btef", ln, params[p + "w1"])
        hidden = jax.nn.gelu(hidden)
        expert_out = jnp.einsum("btef,efd->bted", hidden,
                                params[p + "w2"])
        return jnp.einsum("bted,bte->btd", expert_out, gate)

    def _attend(self, q, k, v, scale, window=None, rope=None):
        return _local_attention(q, k, v, self.mesh, scale=scale,
                                window=window, rope=rope)

    def _layer(self, li, params, x, shared=None, collect=None):
        """Layer ``li`` on (B, T, d), and the shared values it makes
        (``lm_layers.publishes``: a dict, empty for most layers).
        ``shared`` holds the values it reads (``lm_layers.reads``)."""
        c = self.cfg
        made = {}
        attn, ffn = self.layers[li]
        p = "l%d_" % li
        if attn == "mha":
            B, T, d = x.shape
            # --- attention, heads split on tp, sequence ring on sp ------
            with device_scope("l%d/attn" % li):
                q, k, v = self._qkv(params, p, _rms_norm(x))
                q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
                if "sp" in self.axes and self.mesh.shape.get("sp", 1) > 1:
                    att = ring_attention(
                        q, k, v, self.mesh, axis="sp", causal=True,
                        head_axis="tp" if "tp" in self.axes else None,
                        batch_axis="dp" if "dp" in self.axes else None)
                else:
                    att = _local_attention(q, k, v, self.mesh)
                att = att.transpose(0, 2, 1, 3).reshape(B, T, d)
                x = x + att @ params[p + "wo"]
        elif attn == "mla":
            x = lm_layers.kept(x + lm_layers.mla_attention(
                params, li, x, c, self.arch, self._attend), "attn_residual")
        elif attn == "gqa":
            x = lm_layers.kept(x + lm_layers.gqa_attention(
                params, li, x, self.arch, self._attend), "attn_residual")
        else:
            if attn == "ssm":
                branch, made["memory"] = lm_layers.ssm_mixer(
                    params, li, x, self.arch)
            elif attn == "gmu":
                branch = lm_layers.gated_memory(
                    params, li, x, shared["memory"], self.arch)
            else:
                branch, made["kv"] = lm_layers.diff_attention(
                    params, li, x, self.arch, self._attend, shared.get("kv"))
            x = lm_layers.kept(x + branch, "attn_residual")
            made = {k: made[k]
                    for k in lm_layers.publishes(li, attn, self.arch)}
        if ffn == "soft_moe":
            # --- MoE FFN: soft top-2-ish gate over ep-sharded experts ---
            with device_scope("l%d/ffn" % li):
                return x + self._moe_ffn(params, p, x), made
        if ffn == "swiglu":
            return x + lm_layers.swiglu_ffn(params, li, x, self.arch), made
        out, sent = self._routed_ffn(params, li, x)
        if collect is not None:
            collect.append((li, sent))
        return x + out, made

    def _routed_ffn(self, params, li, x):
        """Layer ``li``'s routed FFN on (B, T, d) and what the router sent
        here (``lm_layers.moe_ffn``: the pairs summed over the devices, the
        live tiles of the fullest device's layout). ``pallas_call`` has no
        GSPMD partitioning rule, so on a dp mesh every device routes its
        own rows and runs the grouped matmul on them, in the layout its
        own routing fits, under ``shard_map``, the weights replicated (as
        :func:`_local_attention` does for the flash kernels)."""
        import jax

        sub = {n: params[n] for n in lm_layers.layer_table(
            li, (None, "moe"), self.cfg, self.arch)}

        def local(sub, x):
            return lm_layers.moe_ffn(sub, li, x, self.arch)

        if dict(self.mesh.shape).get("dp", 1) == 1:
            return local(sub, x)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def one_device(sub, x):
            out, sent = local(sub, x)
            return out, {"counts": jax.lax.psum(sent["counts"], "dp"),
                         "live_tiles": jax.lax.pmax(sent["live_tiles"], "dp")}

        return shard_map(one_device, mesh=self.mesh, in_specs=(P(), P("dp")),
                         out_specs=(P("dp"), P()), check_vma=False)(sub, x)

    def _forward(self, params, tokens, collect=None):
        import jax
        import jax.numpy as jnp

        with device_scope("embed"):
            x = params["embed"][tokens]  # (B, T, d)
        # what a layer makes for later layers to read rides beside x: a
        # recomputed maker hands it out as an output, a recomputed reader
        # takes it as an input, so no reader's backward makes it again and
        # its gradient is the readers' summed
        shared = {}
        for li in range(len(self.layers)):
            read = {k: shared[k] for k in lm_layers.reads(
                li, self.layers[li][0], self.arch)}
            if self.remat and collect is None:
                names = [n for n in params if n.startswith("l%d_" % li)]
                x, made = jax.checkpoint(lm_layers.recomputed(
                    lambda sub, x, read, li=li: self._layer(
                        li, sub, x, read)),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *lm_layers.KEPT_BY_A_RECOMPUTED_LAYER))(
                    {n: params[n] for n in names}, x, read)
            else:
                x, made = self._layer(li, params, x, read, collect)
            shared.update(made)
        with device_scope("head_loss"):
            ln = (_rms_norm(x) if self.classic else lm_layers.norm(
                params, "final_norm", x, self.arch))
            if self.arch.get("tied_head"):
                return jnp.einsum("btd,vd->btv", ln, params["embed"])
            logits = ln @ params["out_w"]
        return logits

    def routing_stats(self, params, tokens):
        """Per expert layer, what the router sends to the experts held
        here at these tokens: ``{"layer", "pairs_held", "load" (each held
        expert's pairs, over all devices), "row_budget" and
        "compact_budget" (of one device's layout), "live_rows" (of the
        fullest device's layout: its groups padded to whole tiles), "fits"
        (whether every device's step runs the layer in the compact
        layout)}``. A jitted forward of its own, outside the step: which
        layout a step takes is decided on the device."""
        import jax

        if self._stats_jit is None:
            def stats(params, tokens):
                collect = []
                self._forward(params, tokens, collect)
                return dict(collect)

            self._stats_jit = jax.jit(stats)
        m = self.arch["moe"]
        lo, hi = m["experts_held"]
        moe = lm_layers._moe
        n_tokens = tokens.size // dict(self.mesh.shape).get("dp", 1)
        budget = moe.row_budget(n_tokens, m["top_k"], hi - lo,
                                moe.GMM_BLOCK_ROWS)
        compact = moe.compact_row_budget(n_tokens, m["top_k"], hi - lo,
                                         m["n_experts"], moe.GMM_BLOCK_ROWS)
        out = []
        for li, sent in sorted(jax.device_get(
                self._stats_jit(params, tokens)).items()):
            live = int(sent["live_tiles"][0]) * moe.GMM_BLOCK_ROWS
            out.append({"layer": li, "pairs_held": int(sent["counts"].sum()),
                        "load": [int(n) for n in sent["counts"]],
                        "row_budget": budget, "compact_budget": compact,
                        "live_rows": live, "fits": live <= compact})
        return out

    def _serving_only_classic(self):
        if not self.classic:
            raise NotImplementedError(
                "the serving forwards (prefill/decode/verify) run the "
                "('mha', 'soft_moe') block only: a latent (MLA) layer needs "
                "a latent KV cache, a grouped-query layer a page pool by "
                "k/v head (and a windowed one pages that are released), "
                "and a routed layer a serving dispatch, which this model "
                "does not have yet (ROADMAP Reach A3, A5, A6)")

    # --- incremental decode (generation subsystem) ------------------------
    def prefill_forward(self, params, tokens, attend=None):
        """Full causal forward over a (B, T) prompt that ALSO returns the
        per-layer K/V it computed — the prefill half of the generation
        subsystem's prefill/decode split (serving/generation/).

        Returns ``(logits, ks, vs)``: fp32 logits (B, T, V) and stacked
        projections (L, B, T, H, hd) in cache storage layout. T is a
        prefill *bucket* length — rows at or beyond the true prompt
        length are causal-masked garbage the caller never reads (and the
        pages they land in are overwritten/masked by the decode step).
        Attention runs the Pallas flash kernel on TPU (same bucketed
        compile-key discipline as serving) and an fp32 dense reference
        elsewhere — the same fp32 softmax discipline as
        :func:`~.flash_attention.paged_decode_attention`, so incremental
        decode reproduces this forward token-exactly.

        ``attend(li, q, k, v) -> (B, H, T, hd)`` (optional) replaces the
        per-layer attention — the serving control plane's suffix prefill
        passes a hook that additionally attends to a cached prompt
        prefix in the paged KV pool (docs/serving_control.md); this
        model has no positional encoding, so suffix tokens need no
        position offset, only the hook's extended key set. The layer
        math around the hook (projections, MoE FFN, norms) stays THE
        shared implementation, so training checkpoints serve unchanged
        on every path.
        """
        self._serving_only_classic()
        import jax.numpy as jnp

        c = self.cfg
        B, T = tokens.shape
        d = c["d_model"]
        x = params["embed"][tokens]
        ks, vs = [], []
        for li in range(c["n_layers"]):
            p = "l%d_" % li
            q, k, v = self._qkv(params, p, _rms_norm(x))
            ks.append(k)
            vs.append(v)
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
            att = (_prefill_attention(q, k, v) if attend is None
                   else attend(li, q, k, v))
            att = att.transpose(0, 2, 1, 3).reshape(B, T, d)
            x = x + att @ params[p + "wo"]
            x = x + self._moe_ffn(params, p, x)
        logits = (_rms_norm(x) @ params["out_w"]).astype(jnp.float32)
        return logits, jnp.stack(ks), jnp.stack(vs)

    def decode_forward(self, params, tokens, attend):
        """One incremental-decode layer stack over a slot batch.

        ``tokens``: (S,) int32 — each active slot's previous token;
        ``attend(li, q, k_new, v_new) -> (S, H, hd)`` — the caller-owned
        attention hook: the generation engine scatters ``k_new/v_new``
        into its paged KV cache and runs
        :func:`~.flash_attention.paged_decode_attention` against it.
        The weight math (projections, MoE FFN, norms) is shared with
        ``_forward``/``prefill_forward``, so any checkpoint that trains
        here decodes here. Returns fp32 logits (S, V).
        """
        self._serving_only_classic()
        import jax.numpy as jnp

        c = self.cfg
        S = tokens.shape[0]
        d = c["d_model"]
        x = params["embed"][tokens]  # (S, d)
        for li in range(c["n_layers"]):
            p = "l%d_" % li
            q, k, v = self._qkv(params, p, _rms_norm(x)[:, None, :])
            att = attend(li, q[:, 0], k[:, 0], v[:, 0])   # (S, H, hd)
            x = x + att.reshape(S, d) @ params[p + "wo"]
            x = x + self._moe_ffn(params, p, x[:, None, :])[:, 0]
        return (_rms_norm(x) @ params["out_w"]).astype(jnp.float32)

    def verify_forward(self, params, tokens, attend):
        """Batched-verify layer stack for speculative decoding: Q = k+1
        candidate positions per slot in ONE forward (a short-prefill
        shape, not Q sequential decode calls — docs/generation.md).

        ``tokens``: (S, Q) int32 — each slot's last committed token
        followed by its k draft candidates; ``attend(li, q, k_new,
        v_new) -> (S, Q, H, hd)`` — the caller-owned hook (all arrays in
        cache storage layout (S, Q, H, hd)): the generation engine
        scatters all Q keys/values into its paged pool optimistically
        and runs :func:`~.flash_attention.paged_verify_attention`, whose
        per-query causal limit reproduces Q sequential decode steps.
        The weight math is the same shared ``_qkv``/``_moe_ffn``/norm
        implementation as every other path (this model has no positional
        encoding, so candidate positions need no offset). Returns fp32
        logits (S, Q, V).
        """
        self._serving_only_classic()
        import jax.numpy as jnp

        c = self.cfg
        S, Q = tokens.shape
        d = c["d_model"]
        x = params["embed"][tokens]  # (S, Q, d)
        for li in range(c["n_layers"]):
            p = "l%d_" % li
            q, k, v = self._qkv(params, p, _rms_norm(x))  # (S, Q, H, hd)
            att = attend(li, q, k, v)                     # (S, Q, H, hd)
            x = x + att.reshape(S, Q, d) @ params[p + "wo"]
            x = x + self._moe_ffn(params, p, x)
        return (_rms_norm(x) @ params["out_w"]).astype(jnp.float32)

    def loss_fn(self, params, tokens, targets):
        import jax
        import jax.numpy as jnp

        logits = self._forward(params, tokens)
        with device_scope("head_loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(nll)

    # --- compiled train step ----------------------------------------------
    def step_fn(self, lr=0.1):
        """Compiled ``(params, tokens, targets) -> (params, loss)`` with
        ``lr`` bound.

        The learning rate enters the program as a TRACED argument, so
        every lr value shares ONE compiled step — a graftlint G002
        finding fixed: the old closure-captured ``lr`` compiled a fresh
        program per distinct value, which under a per-step schedule
        meant a recompile every step. ``_step_cache`` now only holds
        tiny binding wrappers (callers rely on ``step_fn(lr=x) is
        step_fn(lr=x)``)."""
        import jax

        lr = float(lr)
        if self._step_jit is None:
            def step(params, tokens, targets, lr):
                loss, grads = jax.value_and_grad(self.loss_fn)(
                    params, tokens, targets)
                with device_scope("update"):
                    new_params = {k: (params[k] - lr * grads[k]).astype(
                        params[k].dtype) for k in params}
                return new_params, loss

            self._step_jit = jax.jit(
                step, donate_argnums=(0,),
                out_shardings=(self.param_shardings(), None))
        if lr not in self._step_cache:
            step_jit = self._step_jit

            def bound(params, tokens, targets, _lr=lr):
                with trace_span("transformer.step", "parallel",
                                step=self._step_calls):
                    self._step_calls += 1
                    with trace_span("transformer.enqueue", "parallel"):
                        return step_jit(params, tokens, targets, _lr)

            self._step_cache[lr] = bound
        return self._step_cache[lr]

    def shard_batch(self, tokens, targets):
        """Tokens batch-sharded on dp, sequence on sp."""
        import jax

        sh = self._ns("dp", "sp")
        return jax.device_put(tokens, sh), jax.device_put(targets, sh)

    # --- checkpoint / resume ----------------------------------------------
    def save_checkpoint(self, params, path):
        """Write the sharded parameter tree to ``path`` (.npz). Arrays
        are gathered to host via `multihost_utils.process_allgather`
        when any shard lives on another process, so tp/ep-sharded
        tensors checkpoint whole; process 0 writes, all fence."""
        import jax

        host = {}
        for k, v in params.items():
            if getattr(v, "is_fully_addressable", True):
                host[k] = np.asarray(v)
            else:
                from jax.experimental import multihost_utils

                host[k] = np.asarray(
                    multihost_utils.process_allgather(v, tiled=True))
            if host[k].dtype.kind == "V":
                # bfloat16 is not a dtype .npz can name (it comes back
                # as raw void bytes); it round-trips exactly through
                # fp32, and load_checkpoint casts to the model's dtype
                host[k] = host[k].astype(np.float32)
        from .mesh import write_and_fence

        write_and_fence(
            lambda: np.savez(path if path.endswith(".npz")
                             else path + ".npz", **host),
            "tp_ckpt_%s" % path)

    def load_checkpoint(self, path):
        """Rebuild the parameter tree with this instance's shardings
        (each device receives only its shard)."""
        import jax

        shardings = self.param_shardings()
        if not path.endswith(".npz"):
            path += ".npz"
        with np.load(path) as z:
            missing = set(shardings) - set(z.files)
            if missing:
                raise ValueError("checkpoint %r missing parameters: %s"
                                 % (path, sorted(missing)))
            return {k: jax.device_put(
                        np.asarray(z[k], dtype=self.dtype), shardings[k])
                    for k in shardings}


def _prefill_attention(q, k, v):
    """Causal attention for the generation prefill: the Pallas flash
    kernel on TPU (T permitting), else a dense reference with the fp32
    softmax discipline of ``paged_decode_attention`` — scores, softmax
    and the PV contraction all accumulate in fp32 regardless of the
    storage dtype, so prefill rows and decode steps agree token-exactly
    (bf16 included: the cached K/V are bit-identical to a recompute, and
    the fp32 attention arithmetic matches on both sides)."""
    import jax
    import jax.numpy as jnp

    T, d = q.shape[2], q.shape[3]
    if jax.default_backend() == "tpu" and T >= 128:
        from .flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    scale = float(1.0 / np.sqrt(d))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def _local_attention(q, k, v, mesh=None, scale=None, window=None, rope=None):
    """Non-sequence-sharded attention: the Pallas flash kernel on TPU
    (forward AND backward tiled — no T x T HBM materialization in
    training either), XLA reference elsewhere. k and v may have fewer
    heads than q (grouped-query), ``window`` bounds how far back a
    query sees, and ``rope`` rotates q and k — in the kernels on the TPU,
    in XLA before the formula elsewhere (``flash_attention``).

    pallas_call has no GSPMD partitioning rule, so on a dp/tp-sharded
    mesh the kernel runs under shard_map: attention is embarrassingly
    parallel over batch (dp) and heads (tp), each device invoking the
    kernel on its local shard. Meshes with other sharded axes (or
    non-divisible batch/head counts) keep the XLA formula, which GSPMD
    partitions correctly."""
    import jax

    B, H, T, _ = q.shape
    if jax.default_backend() == "tpu" and T >= 128:
        from .flash_attention import flash_attention

        if mesh is None or mesh.devices.size == 1:
            return flash_attention(q, k, v, causal=True, scale=scale,
                                   window=window, rope=rope)
        axes = dict(mesh.shape)
        ndp, ntp = axes.get("dp", 1), axes.get("tp", 1)
        sharded = {a for a, s in axes.items() if s > 1}
        if (sharded <= {"dp", "tp"} and B % ndp == 0 and H % ntp == 0
                and k.shape[1] % ntp == 0):
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            spec = P("dp" if ndp > 1 else None,
                     "tp" if ntp > 1 else None, None, None)
            fn = shard_map(
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                scale=scale, window=window,
                                                rope=rope),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
            return fn(q, k, v)
    if rope is not None:
        from .flash_attention import rotated

        q, k = rotated(q, rope), rotated(k, rope)
    if window is None and k.shape[1] == H:
        from .ring_attention import attention_reference

        return attention_reference(q, k, v, causal=True, scale=scale)
    from .flash_attention import _dense_with_lse

    return _dense_with_lse(q, k, v, causal=True, scale=scale,
                           window=window)[0]


def _laguna(cfg, moe):
    """(layers, arch) of ``model_type: laguna`` from its published keys:
    ``layer_types`` and ``num_attention_heads_per_layer`` give each layer
    its mask (``sliding_window`` positions or all of them), its query
    heads over ``num_key_value_heads`` k/v heads of ``head_dim``, and its
    block of ``rope_parameters``; ``mlp_layer_types`` its FFN. The router
    has no expert bias, and the shared expert its own width."""
    sliding = {"full_attention": None,
               "sliding_attention": cfg["sliding_window"]}

    def rope(kind):
        r = dict(cfg["rope_parameters"][kind])
        r["theta"] = r.pop("rope_theta")
        if r.pop("rope_type", "default") == "default":
            r.pop("factor", None)
        else:
            r.setdefault("original_max_position_embeddings", cfg[
                "rope_parameters"]["original_max_position_embeddings"])
        return r

    n = cfg["num_hidden_layers"]
    kinds, heads, ffns = (cfg[k][:n] for k in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types"))
    layers = [("gqa", "swiglu" if f == "dense" else "moe") for f in ffns]
    arch = {"rms_norm_eps": cfg["rms_norm_eps"],
            "gqa": {"n_kv_heads": cfg["num_key_value_heads"],
                    "head_dim": cfg["head_dim"], "gate": bool(cfg["gating"]),
                    "layers": [{"n_heads": h, "window": sliding[k],
                                "rope": rope(k)}
                               for k, h in zip(kinds, heads)]},
            "moe": dict(moe, scale=cfg["moe_routed_scaling_factor"],
                        n_shared=1, router_bias=False,
                        d_shared=cfg["shared_expert_intermediate_size"])}
    return layers, arch


def _phi4flash(cfg):
    """(layers, arch) of ``model_type: phi4flash`` (a decoder-hybrid-
    decoder) from its published keys. By published index ``l`` of
    ``published.num_hidden_layers`` = 2h layers, ``mb_per_layer`` 2: even
    ``l <= h`` a state-space layer; odd ``l < h`` differential attention
    under ``sliding_window``; ``l = h + 1`` differential attention over the
    whole prefix; then even ``l`` a gated memory unit on layer ``h``'s scan
    output and odd ``l`` differential attention onto layer ``h + 1``'s k
    and v. ``deployment.first_layer`` is the published index of the first
    layer held here (a cut keeps its layers' kinds and ``lambda_init =
    0.8 - 0.6 exp(-0.3 l)``); the sizes the published keys lack stand
    under ``assumed``. Every layer's FFN is a SwiGLU, every norm a
    LayerNorm, the head the embedding transposed."""
    import math

    total = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    half, every = total // 2, cfg["mb_per_layer"]
    first = cfg.get("deployment", {}).get("first_layer", 0)
    assumed = cfg["assumed"]
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds, diff, shared = [], {}, {}
    for li in range(cfg["num_hidden_layers"]):
        pub = first + li        # the layer's published index
        if pub % every == 0:
            kinds.append("ssm" if pub <= half else "gmu")
            if pub == half:
                shared["memory"] = li
            continue
        kinds.append("diff")
        diff[li] = {"window": cfg["sliding_window"] if pub < half else None,
                    "cross": pub > half + 1,
                    "lambda_init": 0.8 - 0.6 * math.exp(-0.3 * pub)}
        if pub == half + 1:
            shared["kv"] = li
    arch = {"layer_norm_eps": cfg["layer_norm_eps"],
            "tied_head": bool(cfg["tie_word_embeddings"]),
            "shared": shared,
            "ssm": {"d_inner": assumed["mamba_expand"] * d,
                    "d_state": assumed["mamba_d_state"],
                    "d_conv": assumed["mamba_d_conv"],
                    "dt_rank": assumed["mamba_dt_rank"]},
            "diff": {"n_heads": H, "n_kv_heads": cfg["num_key_value_heads"],
                     "head_dim": d // H, "subln_eps": 1e-5, "layers": diff}}
    return [(k, "swiglu") for k in kinds], arch


def _rms_norm(x):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                          + 1e-6)
    return (x32 * scale).astype(x.dtype)
