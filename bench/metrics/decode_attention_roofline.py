"""The Pallas decode-attention kernel's share of its roofline: for every
call in the traced window, the larger of the bytes it must move (the keys
and values of each live request's cached rows, its query and output) over
the HBM bandwidth and its FLOPs over the bf16 peak, summed, over the
kernel's summed device time.  Decode attention is bound by the bytes.

The kernel is the decode program's Pallas call (``tpu_custom_call``) whose
result is one bf16 row of heads per slot."""
from bench import counting


def read(summary, job, out):
    c, engine = job.cell.config, job.cell.mix["engine"]
    H = c["num_attention_heads"]
    result = f"= bf16[{engine['slots']},{H},{c['hidden_size'] // H}]"
    times = [d for name, ds in summary.op_events.items()
             if "tpu_custom_call" in name and result in name for d in ds]
    chunks = out.records.get("decode_chunks") or []
    if not times or not chunks:
        return None
    pk = job.peaks
    bound = sum(c["num_hidden_layers"] * max(
        counting.decode_attention_bytes(c, lens) / pk["hbm_bytes_per_s"],
        counting.decode_attention_flops(c, lens) / pk["bf16_flops"])
        for lens in counting.decode_steps(chunks))
    return 100.0 * bound / sum(times)
