"""KV block pool: the most pages in use at once over the window, as a share
of the pool (``BlockPool.used_count / n_blocks``, sampled every 10 ms)."""


def read(obs):
    start, end = obs.window["start_ns"], obs.window["end_ns"]
    shares = [used / total for at, used, total in obs.pool_samples
              if start <= at < end and total]
    return 100.0 * max(shares) if shares else None
