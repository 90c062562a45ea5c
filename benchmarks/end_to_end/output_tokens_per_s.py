"""All output tokens delivered inside the window, over the window's length.
A failed request's tokens earn nothing."""


def read(obs):
    start, end = obs.window["start_ns"], obs.window["end_ns"]
    tokens = sum(1 for log in obs.finished() for t in log.token_ns
                 if start <= t < end)
    return tokens / obs.window_s if tokens else None
