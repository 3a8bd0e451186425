"""Model FLOPs utilisation on DEVICE time: the model FLOPs of the traced
steps (Megatron formula, ``chipbench/flops.py``) over the device's busy
seconds in the trace x chips x peak. The host's and the tracer's cost do
not enter: this is what the compiled step itself reaches."""


def read(params, run):
    steps, t = run.get("trace_steps"), run["trace"]
    if not steps or not run["peaks"] or t["busy_s"] <= 0:
        return None
    model = run["model_flops_per_token"] * run["tokens_per_step"] * steps
    return 100.0 * model / (t["busy_s"] * run["chips"]
                            * run["peaks"]["bf16_flops_per_s"])
