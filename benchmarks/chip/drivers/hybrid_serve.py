"""Energy-bounded serving of a hybrid Mamba2 model (Zamba2): the
``energy_serve`` driver, with the configuration check and the FLOP count of
the hybrid architecture.

The program's registry holds the published depth; the file's depth cut (its
``num_hidden_layers`` and ``hybrid_layer_ids``) and ε are applied to the
config object that the executor and the table are given, and every other
published key must match the program as it is. Each unit also counts its
prefills.
"""

from __future__ import annotations

import dataclasses

from chipbench import work_hybrid
from chipbench.files import BENCH_DIR, load_module

energy_serve = load_module(BENCH_DIR / "drivers" / "energy_serve.py")


class Driver(energy_serve.Driver):
    def _model_config(self, smoke: bool):
        from repro.configs import resolve_config
        from repro.models.ssm import CONV_K

        c = self.config
        mcfg = dataclasses.replace(
            resolve_config(c["arch"], smoke=smoke),
            n_layers=c["num_hidden_layers"],
            hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
            norm_eps=c["rms_norm_eps"])
        hd = c["attention_head_dim"]
        stated = {
            "family": "hybrid", "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "hd": hd,
            "attn_in": c["attention_hidden_size"], "attn_scale": (hd / 2) ** -0.5,
            "d_ff": c["ffn_hidden_size"], "mlp_act": c["hidden_act"],
            "vocab": c["vocab_size"], "tie_embeddings": True,
            "rope_theta": c["rope_theta"], "ssm_state": c["mamba_d_state"],
            "ssm_expand": c["mamba_expand"], "ssm_headdim": c["mamba_headdim"],
            "ssm_ngroups": c["mamba_ngroups"], "ssm_chunk": c["chunk_size"],
            "n_shared_blocks": c["num_mem_blocks"],
            "adapter_rank": c["adapter_rank"]}
        wrong = {k: (getattr(mcfg, k), v) for k, v in stated.items()
                 if getattr(mcfg, k) != v}
        if CONV_K != c["mamba_d_conv"]:
            wrong["mamba_d_conv"] = (CONV_K, c["mamba_d_conv"])
        if c["layers_block_type"] != ["hybrid" if i in mcfg.hybrid_layer_ids
                                      else "mamba" for i in range(mcfg.n_layers)]:
            wrong["layers_block_type"] = (mcfg.hybrid_layer_ids,
                                          c["layers_block_type"])
        if wrong:
            raise ValueError(f"the program's {mcfg.name} differs from the "
                             f"configuration file (program, file): {wrong}")
        return mcfg

    def run_unit(self) -> dict:
        n = len(self.served)
        u = super().run_unit()
        finished = self.served[n:]
        u["prefills"] = len(finished)
        u["flops"] = sum(work_hybrid.request_flops(
            self.config, *s["prompts"].shape, s["tokens"].shape[1])
            for s in finished)
        return u
