"""Model FLOP/s utilization of the Arena environment's CNN training, in %.

Useful samples of the traced window (every device trains gamma1 * gamma2
epochs of whole minibatches per step; epochs computed and masked away
are not counted) times the CNN's training FLOPs per sample
(``flops.cnn_train_flops_per_sample``), per second, over the chip's
bf16 peak."""
import flops


def read(ctx):
    samples = ctx["counts"].get("useful_samples")
    if not samples:
        return None
    per_sample = flops.cnn_train_flops_per_sample(
        flops.mnist_cnn_forward_macs(ctx["config"]["image_size"]))
    rate = samples * per_sample / ctx["counts"]["elapsed_s"] / ctx["chips"]
    return 100.0 * rate / ctx["peaks"]["bf16_flops_per_s"]
