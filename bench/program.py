"""What the benchmark takes from the program: its public entry points and the
model configuration they are given.  Nothing else under ``bench/`` imports
``repro``, and the references import nothing of it.
"""
from __future__ import annotations

from repro.cluster import SliceSpec, Supercomputer  # noqa: F401
from repro.configs.base import (AttentionConfig, ModelConfig,  # noqa: F401
                                OptimizerConfig, ParallelConfig, RunConfig,
                                ShapeConfig)
from repro.optim.adam import init as optimizer_init  # noqa: F401
from repro.train.trainer import TrainerState  # noqa: F401

# one block of the modelled machine; the program runs it on the chips that
# JAX gives this process
SLICE = (4, 4, 4)


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for an OLMo-family configuration file."""
    if c["architecture"] != "olmo":
        raise ValueError(f"no program configuration for {c['architecture']}")
    return ModelConfig(
        name=c["model"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention=AttentionConfig(
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            rope_theta=float(c["rope_theta"])),
        norm="nonparam_ln", act=c["hidden_act"], ffn_glu=True,
        tie_embeddings=c["tie_word_embeddings"],
        max_seq_len=c["max_position_embeddings"],
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"])
