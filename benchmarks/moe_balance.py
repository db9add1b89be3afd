"""Beyond-paper: the paper's load metric applied to MoE expert dispatch.

Routes synthetic tokens through the DeepSeek-MoE router config and reports
expert-load imbalance + dropped-token fraction — the same 'summarized
workload' statistic the paper's beacons communicate, here measured on the
in-model task-mapping problem (see DESIGN.md §4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.models import moe as MOE

from benchmarks.common import csv_row, enable_compile_cache, save, timed


def run(verbose: bool = True) -> dict:
    cfg = reduced_config(get_config("deepseek_moe_16b"),
                         d_model=128, vocab_size=512)
    key = jax.random.PRNGKey(0)
    params = MOE.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 128, cfg.d_model))
    (out, aux), dt = timed(lambda: MOE.apply_moe(params, cfg, x))
    frac = np.asarray(aux["tokens_per_expert"])
    imbalance = float(frac.max() / max(frac.mean(), 1e-9))
    payload = {
        "n_experts": cfg.moe.n_experts,
        "top_k": cfg.moe.top_k,
        "max_over_mean_expert_load": imbalance,
        "dropped_frac": float(aux["dropped_frac"]),
        "load_balance_loss": float(aux["load_balance"]),
    }
    save("moe_balance", payload)
    if verbose:
        csv_row("moe_balance", dt * 1e6,
                f"imbalance={imbalance:.2f}|dropped={payload['dropped_frac']:.3f}")
    return payload


if __name__ == "__main__":
    enable_compile_cache()
    run()
