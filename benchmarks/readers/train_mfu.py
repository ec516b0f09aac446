"""Model FLOP/s utilisation: required operations per token
(``flops_function`` names the function that counts them,
``"<module>.<function>"`` under the benchmark's directory, called as
``fn(dims, seq, program)``) x tokens/s over chips x the chip's published
bf16 peak, in percent."""

from benchmarks import manifest, peaks


def read(facts, ctx, flops_function):
    rate = facts.get("train_tokens_per_s")
    device = facts.get("device") or {}
    if not rate or device.get("platform") != "tpu":
        return None
    per_token = manifest.load_function(flops_function, ctx["bench_dir"])(
        manifest.model_dims(ctx["config"]), int(ctx["mix"]["seq"]),
        ctx["config"]["program"])
    peak = peaks.peaks_for(device["kind"])["bf16_flops"] \
        * int(ctx["cell"]["chips"])
    return 100.0 * per_token * rate / peak
