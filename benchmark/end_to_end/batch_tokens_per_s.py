"""Tokens processed inside the window over the window's length: the prompt
tokens of every request whose first token arrived inside it (its prefill was
done there) plus every generated token received inside it, requests still in
flight at the close included. Counting only completed requests would leave
out the 16 in flight, a seventh of the window's work, by the accident of where
the window closes. host_clock."""


def read(run):
    t_end = run["t0"] + run["seconds"]
    tokens = 0
    for o in run["outcomes"]:
        if o["t_first"] is not None and o["t_first"] <= t_end:
            tokens += o["prompt_tokens"]
        tokens += sum(n for t, n in o["chunks"] if t <= t_end)
    return tokens / run["seconds"]
