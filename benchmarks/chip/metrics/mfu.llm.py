"""Model FLOP/s utilization of hierarchical LLM training, in %.

Required training FLOPs per token (``train_flops_per_token`` of the
reference module the configuration names: for a dense decoder
``flops.llm_train_flops_per_token``, 6 per matmul parameter plus causal
attention; remat recompute and masked epochs not counted) times the
traced window's trained tokens per second per chip, over the chip's bf16
peak. The matmuls run in bf16: the program casts its float32 weights to
the bf16 activations."""
import spec


def read(ctx):
    rate = ctx["rates"].get("train_tokens_per_s")
    if not rate:
        return None
    c = ctx["config"]
    per_token = spec.load_reference(c["reference"]).train_flops_per_token(
        c, ctx["traffic"]["seq_len"])
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
