"""DeepSeek-V2-family serving through the program's ``ServingGateway``:
latent attention (MLA) and a held-experts MoE share.

Everything ``bench/systems/gateway.py`` does, for a configuration in the
published DeepSeek-V2 keys: its ``program_config`` maps them onto the
program's ``ModelConfig``, with the router over every published expert
(``deployment.n_routed_experts_published``) and the
``n_routed_experts`` from ``deployment.expert_offset`` on held here.

The window also reads the gateway's MoE counters (``stats()["moe"]``) at
its start and end: ``moe_rows_routed`` and ``moe_rows_computed`` over
the window, and ``moe_rows_max_expert``, the most rows one held expert
was routed in one layer of one decode step since set-up began.  The
work record counts the held experts' rows from them.
"""

from __future__ import annotations

import numpy as np

from bench.systems import gateway


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file in DeepSeek-V2
    keys; a key the program cannot honour raises."""
    from repro.core.quant import QuantConfig
    from repro.models.config import ModelConfig

    want = {"hidden_act": "silu", "q_lora_rank": None,
            "scoring_func": "softmax", "topk_method": "greedy",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "attention_bias": False, "routed_scaling_factor": 1}
    for k, v in want.items():
        if cfg[k] != v:
            raise ValueError(f"unsupported {k}={cfg[k]!r} (want {v!r})")
    rs, dep, sv = cfg["rope_scaling"], cfg["deployment"], cfg["serving"]
    if rs["type"] != "yarn":
        raise ValueError(f"unsupported rope scaling {rs['type']!r}")
    if rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("unsupported rope_scaling: mscale "
                         f"{rs['mscale']!r} != mscale_all_dim "
                         f"{rs['mscale_all_dim']!r}")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        layer_pattern=("mla",), kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original_len=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        ffn_kind="swiglu", ffn_pattern=("moe",),
        first_k_dense=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        n_experts=dep["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_norm_topk=cfg["norm_topk_prob"],
        n_experts_held=cfg["n_routed_experts"],
        expert_offset=dep["expert_offset"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], compute_dtype=sv["compute_dtype"],
        l2r=QuantConfig(**sv["l2r"]))


class System(gateway.System):
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.serve import ServingGateway
        from repro.serve.engine import prepare_params

        sv = self.cfg["serving"]
        self.pc = program_config(self.cfg)
        self.raw = self.model.make_weights(self.cfg, self.seed)
        self.params = prepare_params(self.pc, self.raw)
        self.buckets = gateway.reachable_buckets(self.mix)
        self.gw = ServingGateway(
            self.pc, self.params, n_slots=self.sv["n_slots"],
            max_len=self.sv["max_len"],
            cache_dtype=jnp.dtype(sv["cache_dtype"]),
            progressive=sv["progressive"], early_exit=sv["early_exit"],
            prefill_group=self.sv["prefill_group"], buckets=self.buckets,
            default_class=gateway.precision_class(sv["default_class"]))
        self._warm()
        self.out_at_open: dict[int, int] = {}
        self._uid = 0
        if self.mix["kind"] == "closed_loop":
            self._fill()
        jax.block_until_ready(self.gw.state)

    def window(self, seconds: float, tracer) -> dict:
        c0 = self.gw.stats(latency=False)["moe"]
        counts = super().window(seconds, tracer)
        c1 = self.gw.stats(latency=False)["moe"]
        counts.update(
            moe_rows_routed=c1["rows_routed"] - c0["rows_routed"],
            moe_rows_computed=c1["rows_computed"] - c0["rows_computed"],
            moe_rows_max_expert=c1["rows_max_expert"])
        self.moe_counts = counts
        return counts

    # ------------------------------------------------------- work counts
    def work(self) -> dict:
        """Model work of everything the gateway did from the window's
        start, as ``gateway.System.work``: decode rows and prefill rows
        at their true lengths, each through the L2R matmuls every token
        makes (and the head) at int8, through MLA's latent up-projection,
        the router and attention (decompressed form) in float; and the
        held experts' rows at int8, counted by the program's counters in
        decode and at their expected share in prefill."""
        m, cfg = self.model, self.cfg
        gemms = m.token_gemms(cfg)
        layer = sum(k * n for name, k, n in gemms if name != "head")
        head = sum(k * n for name, k, n in gemms if name == "head")
        fl = sum(k * n for _, k, n in m.float_gemms(cfg))
        expert = sum(k * n for _, k, n in m.expert_gemms(cfg))
        moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        attn1 = m.attn_macs(cfg, 1)
        dec = {"rows": 0, "int8_ops": 0.0, "bf16_flops": 0.0}
        pre = {"rows": 0, "int8_ops": 0.0, "bf16_flops": 0.0}
        for r in self.touched:
            p, n = len(r.prompt), len(r.output)
            n0 = self.out_at_open.get(r.uid, 0)
            if n0 == 0:
                pre["rows"] += p
                pre["int8_ops"] += 2.0 * (
                    p * (layer + moe_layers * m.routed_per_token(cfg)
                         * expert) + head)
                pre["bf16_flops"] += 2.0 * (p * fl
                                            + attn1 * p * (p + 1) / 2)
            a = max(1, n0)  # decode token j (a..n-1) attends over p + j
            if n > a:
                dec["rows"] += n - a
                dec["int8_ops"] += 2.0 * (n - a) * (layer + head)
                dec["bf16_flops"] += 2.0 * ((n - a) * fl + attn1 * (
                    (n - a) * p + (a + n - 1) * (n - a) / 2))
        routed = self.moe_counts["moe_rows_routed"]
        dec["int8_ops"] += 2.0 * routed * expert
        dec["expert_rows"] = routed
        dec["executions"] = self.executions["decode"]
        pre["executions"] = self.executions["prefill"]
        dec["module"], pre["module"] = gateway.DECODE_MODULE, \
            gateway.PREFILL_MODULE
        pre["positions"] = self._prefill_positions(pre["executions"])
        out_bytes = 2 if cfg["serving"]["compute_dtype"] == "bfloat16" else 4
        return {"decode": dec, "prefill": pre, "out_bytes": out_bytes,
                "layer_gemms": [[k, n] for name, k, n in gemms
                                if name != "head"],
                "expert_gemms": [[k, n] for _, k, n in m.expert_gemms(cfg)],
                "experts_held": cfg["n_routed_experts"],
                "moe_layers": moe_layers}

    # -------------------------------------------------------------- check
    def served_gaps(self, reqs) -> tuple[float, float]:
        """As ``gateway.System.served_gaps``, with the nested rope and
        deployment groups passed to the reference."""
        import jax.numpy as jnp

        key = self.model.cfg_key(self.cfg)
        worst, shares = 0.0, []
        for r in reqs:
            seq = self.padded(r)
            picks = np.concatenate([seq[1:], seq[:1]])
            gaps = np.asarray(self.model.reference_gaps(
                key, self.raw, jnp.asarray(seq), jnp.asarray(picks)))
            p, n = len(r.prompt), len(r.output)
            seg = gaps[p - 1:p - 1 + n]
            worst = max(worst, float(np.max(seg)) if np.isfinite(seg).all()
                        else 1e30)
            shares.append(float(np.mean(~(seg <= 0))))
        return worst, float(np.median(shares))
