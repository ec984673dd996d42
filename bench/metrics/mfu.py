"""Model FLOP utilization: the operations of the requests completed
(counted from the configuration's shapes) over the window times the
chips' published bf16 peak."""


def read(run):
    per_request = sum(rep * sum(c.flops for c in calls)
                      for _, rep, calls in run["stage_calls"](
                          run["config"], 1, run["prompt_len"]))
    peak = run["chips"] * run["peaks"]["flops_per_s"]
    return 100.0 * per_request * run["completed"] / (run["span_s"] * peak)
