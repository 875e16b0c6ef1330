"""Model step (prefill): device time of the `jit_prefill` programs in the
traced window over the thousands of prompt tokens prefilled in it (requests
whose first token the replica stamped inside the traced window).
device_trace."""


def read(run):
    data, marks = run["trace_data"], run["marks"]
    if data is None or "trace_start" not in marks:
        return None
    by_id = {str(o["index"]): o for o in run["outcomes"]}
    tokens = sum(by_id[i]["prompt_tokens"]
                 for i, s in run["replica"]["stamps"].items()
                 if i in by_id
                 and marks["trace_start"] <= s[1] <= marks["trace_stop"])
    d = data.module_durations("jit_prefill")
    if not tokens or not d:
        return None
    return sum(d) * 1e3 / (tokens / 1e3)
