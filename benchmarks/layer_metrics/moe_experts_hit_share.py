"""Routed expert layer: of the experts a decode dispatch holds (expert
layers x micro-steps x experts), the share that got a token, mean over the
window's decode dispatches. From the histogram each step returns
(stepscope ``experts_hit`` / ``experts_held``). It is what decides a decode
step's needed bytes: lower = fewer experts to read for the same tokens.
None where no decode record carries routing counters (another family)."""


def read(obs):
    shares = [r["experts_hit"] / r["experts_held"]
              for r in obs.decode_steps()
              if r.get("experts_held") and r.get("routed_tokens")]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
