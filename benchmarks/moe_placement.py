"""Beyond-paper: traffic-aware expert placement on a reduced MoE.

Trains the reduced deepseek-moe config twice — replica cache OFF (pure
all-to-all) vs ON (Redynis daemon managing R hot slots per layer) — and
reports: replica-cache hit rate over training, token-drop rates, and the
analytic all-to-all bytes per step each configuration implies at the
production shard sizes (the serving-side numbers the dry-run corroborates).
"""

from __future__ import annotations

import dataclasses

import jax

from benchmarks.common import banner, emit, enable_compile_cache
from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, Pipeline
from repro.models import build
from repro.models.moe import cold_capacity
from repro.train.optim import OptConfig
from repro.train.trainer import TrainConfig, Trainer


def a2a_bytes_per_layer(cfg, tokens_per_group: int, groups: int) -> float:
    """Dispatch + combine payload of the cold path: 2 × [E, C, D] buffers."""
    c = cold_capacity(cfg, tokens_per_group)
    return 2.0 * groups * cfg.num_experts * c * cfg.d_model * 2  # bf16


def main(steps: int = 40) -> None:
    banner("moe_placement: hot-expert replica cache (Redynis integration #1)")
    base = dataclasses.replace(
        reduced(get_config("deepseek-moe-16b")), sweep_period=5
    )
    pipe_cfg = DataConfig(vocab_size=base.vocab_size, seq_len=64, global_batch=8, zipf_a=1.3)

    for label, cfg in (
        ("baseline_a2a", dataclasses.replace(base, hot_expert_slots=0)),
        ("redynis_hot", base),
    ):
        model = build(cfg)
        tr = Trainer(
            model,
            TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=5, total_steps=steps), log_every=1000),
            num_nodes=4,
        )
        st = tr.init_state(jax.random.PRNGKey(0))
        st, hist = tr.run(st, Pipeline(pipe_cfg), steps, log=False)
        hot = [h.get("moe_hot_frac", 0.0) for h in hist]
        drop = [h.get("moe_dropped", 0.0) for h in hist]
        emit(
            "moe_placement",
            round(hist[-1]["loss"], 4),
            "final_loss",
            mode=label,
            hot_frac_last10=round(sum(hot[-10:]) / 10, 3),
            dropped_last10=round(sum(drop[-10:]) / 10, 3),
        )
        bytes_l = a2a_bytes_per_layer(cfg, tokens_per_group=512, groups=2048)
        emit(
            "moe_a2a_bytes_per_layer",
            round(bytes_l / 1e6, 1),
            "MB@prod-shapes",
            mode=label,
        )
        if label == "redynis_hot":
            hr = float(tr.expert_daemon.hit_rate(st.expert_placement))
            emit("moe_replica_hit_rate", round(hr, 3), "frac", mode=label)


if __name__ == "__main__":
    enable_compile_cache()
    main()
