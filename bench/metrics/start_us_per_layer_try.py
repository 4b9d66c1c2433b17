"""Host start generation per layer and try: the summed length of the
`search.starts` spans ended in the window over the sum, span by span,
of their oracle-checked start tries (`tries` attr) times the layers
searched (`layers` attr).  Each try maps every layer with CoSA and
prices the network, so this is the cost of one layer's start.
Nothing where no span carries both attrs."""


def read(run):
    spent, work = 0.0, 0
    for s in run.spans_in_window("search.starts"):
        tries, layers = s["attrs"].get("tries"), s["attrs"].get("layers")
        if tries is None or layers is None:
            continue
        spent += s["t_end"] - s["t_start"]
        work += tries * layers
    return 1e6 * spent / work if work else None
