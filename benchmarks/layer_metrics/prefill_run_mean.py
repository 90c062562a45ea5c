"""Engine scheduler: how many prefill-chunk dispatches the loop issues back
to back while decode is held up. Between two consecutive decode dispatches
that both carried a request (stepscope records in start order) it counts the
`prefill_chunk` records; the metric is the mean of those counts over the
intervals that hold at least one chunk (a run), 0 where no interval does.
A loop that puts a decode step after every chunk reads 1."""


def read(obs):
    steps = sorted((r for r in obs.steps
                    if r["phase"] in ("decode", "prefill_chunk")),
                   key=lambda r: r["start_ns"])
    runs, run, decodes = [], None, 0
    for r in steps:
        if r["phase"] == "decode":
            if r["batch_size"] < 1:
                run = None          # the interval is not between two such
                continue
            decodes += 1
            if run:
                runs.append(run)
            run = 0
        elif run is not None:
            run += 1
    if decodes < 2:
        return None
    return sum(runs) / len(runs) if runs else 0.0
