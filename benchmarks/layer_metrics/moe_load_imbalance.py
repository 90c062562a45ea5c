"""Routed expert layer: in the window's prefill-chunk dispatches, the most
tokens one expert of one layer got over the mean per expert (stepscope
``expert_load_max`` / ``expert_load_mean``), as the sum of the one over the
sum of the other: a dispatch counts by its tokens, so a prompt's last chunk
of a few rows (where one token on an expert is already many times the mean)
does not drown the full chunks. 1 = even; the grouped product's longest
group sets its tail. None where no chunk record carries routing counters
(another family)."""


def read(obs):
    chunks = [r for r in obs.steps
              if r["phase"] == "prefill_chunk" and r.get("expert_load_mean")]
    if not chunks:
        return None
    return (sum(r["expert_load_max"] for r in chunks)
            / sum(r["expert_load_mean"] for r in chunks))
