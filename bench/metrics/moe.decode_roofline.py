"""The decode chunk's share of its roofline on a model whose MoE layers hold
a share of the experts (``moe.decode_roofline``): the least time a chunk
could take, the larger of the bytes it must move over the HBM bandwidth
and its FLOPs over the bf16 peak, over the device time of the decode
program (``_decode``).  Both are means per chunk over the traced window.

Per decode step the chunk must move, in bf16:

- every weight outside the experts once (the mixers, the dense FFNs, the
  routers, the norms and the tied embedding as the output head);
- the three matrices of each held expert that the step's live tokens
  touched, once (the ``touched`` of the engine's ``serve.decode.moe``
  span, summed over the MoE layers);
- the keys and values of each live request's cached rows in the
  attention layers, and its conv state, read and written.

Its FLOPs are the live tokens' products with the weights outside the
experts, the routed token-expert pairs' products with the held experts
(the span's ``pairs``), and attention.  These are lower bounds of what
the program does (it computes every held expert for every slot), so the
share cannot pass 100%.  Without those spans (a model without held
experts, or a program that does not report them) nothing is read."""
from bench import spans as SP

BF16 = 2
DECODE = "_decode"


def _sizes(c: dict) -> dict:
    types = c["layer_types"]
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    n_attn = sum(t == "full_attention" for t in types)
    n_conv = len(types) - n_attn
    n_dense = c["num_dense_layers"]
    n_moe = len(types) - n_dense
    E = c["published"]["num_experts"]
    products = (n_attn * (D * H * hd + 2 * D * KH * hd + H * hd * D)
                + n_conv * 4 * D * D
                + n_dense * 3 * D * c["intermediate_size"]
                + n_moe * D * E
                + c["vocab_size"] * D)
    others = (n_attn * 2 * hd + n_conv * c["conv_L_cache"] * D
              + 2 * len(types) * D + D)
    return {"products": products, "weights": products + others,
            "expert": 3 * D * F, "n_attn": n_attn,
            "kv_row": n_attn * 2 * KH * hd,
            "conv_state": n_conv * (c["conv_L_cache"] - 1) * D,
            "attn_flops": 4 * n_attn * H * hd}


def read(summary, job, out):
    sp = SP.for_job(job)
    moe = sp.named("serve.decode.moe") if sp else []
    if not moe:
        return None
    runs = summary.program_s(DECODE)
    chunks = out.records.get("decode_chunks") or []
    if not runs or not chunks:
        return None
    c, pk = job.cell.config, job.peaks
    s = _sizes(c)
    # per chunk, from the engine's spans: steps, touched experts, pairs
    w_bytes = sum(m.args["steps"] * s["weights"] + m.args["touched"]
                  * s["expert"] for m in moe) * BF16 / len(moe)
    e_flops = sum(2 * s["expert"] * m.args["pairs"] for m in moe) / len(moe)
    # per chunk, from the driver's record of each live request's cached
    # length at each step it advanced
    tokens = sum(len(lens) for chunk in chunks for lens in chunk)
    rows = sum(sum(lens) for chunk in chunks for lens in chunk)
    kv_bytes = (rows * s["kv_row"] + 2 * tokens * s["conv_state"]) * BF16
    flops = 2 * s["products"] * tokens + s["attn_flops"] * rows
    bound = max((w_bytes + kv_bytes / len(chunks)) / pk["hbm_bytes_per_s"],
                (e_flops + flops / len(chunks)) / pk["bf16_flops"])
    return 100.0 * bound / (sum(runs) / len(runs))
