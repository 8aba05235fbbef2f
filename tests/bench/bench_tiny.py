"""A cell of the benchmark cut to a size a CPU test run can hold.

The limits are this size's own, set like the cells' (section 6 of PERF.md)
between the program's readings here and the control's: the program reads
about 0.002 of a logit and 2e-4 to 1e-3 on the training gaps; the control
0.25-0.33 of a logit and 5e-3 to 7e-3."""
import copy
import time

from bench import harness as H

TINY = {"model": "olmo-tiny", "architecture": "olmo",
        "program": {"config": "olmo-1b", "replace": {
            "name": "olmo-tiny", "num_layers": 2, "d_model": 64,
            "d_ff": 128, "vocab_size": 512, "max_seq_len": 128,
            "attention.num_heads": 4, "attention.num_kv_heads": 4,
            "attention.head_dim": 16}},
        "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 512, "max_position_embeddings": 128,
        "rope_theta": 10000.0, "hidden_act": "silu",
        "tie_word_embeddings": True, "param_dtype": "float32",
        "compute_dtype": "bfloat16", "initializer_range": 0.125}


def serve_cell(config=None, **mix):
    cell = H.find_cell(H.load_benchmark(), "olmo1b-serve-chat")
    cell.config = copy.deepcopy(config or TINY)
    cell.reference = H.reference(cell.config["architecture"])
    cell.mix.update(
        rate_per_s=6.0,
        prompt_len={"dist": "lognormal", "median": 16, "sigma": 0.7,
                    "min": 4, "max": 32},
        output_len={"dist": "lognormal", "median": 8, "sigma": 0.7,
                    "min": 2, "max": 24},
        engine={"slots": 3, "max_len": 64, "prompt_len": 32, "chunk": 4,
                "kv_block": 16, "kv_share": False, "kv_blocks": 12,
                "suffix_len": 16},
        trace_from_s=0.2, trace_s=0.5)
    cell.mix.update(mix)
    cell.limits.update(sample_requests=3, max_logit_gap=0.05)
    return cell


def train_cell(config=None, **mix):
    cell = H.find_cell(H.load_benchmark(), "olmo1b-train-2k")
    cell.config = copy.deepcopy(config or TINY)
    cell.reference = H.reference(cell.config["architecture"])
    cell.mix.update(batch=4, seq_len=32, block_steps=2, trace_from_s=0.2)
    cell.mix.update(mix)
    cell.limits.update(first_grad_gap=0.004, update_gap=0.003,
                       update_gap_median=0.003)
    return cell


def run(cell, seed=2**33 + 5, seconds=1.5, control=False, tracer=None):
    """Drive ``cell`` past the harness's look for a chip; returns the
    driver's outcome."""
    job = H.Job(cell=cell, seed=seed, seconds=seconds,
                t_start=time.perf_counter(), tracer=tracer or H.Tracer(False),
                control=control)
    return H.driver(cell.mix["kind"]).run(job)
