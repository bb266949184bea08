"""Share of the window's slot-iterations that consumed a prompt token: a
count.  Every active slot consumes one token per iteration and makes one
unless it is still in its prompt, so the share is 1 - generated tokens (the
program's counter over the window) / sum of the iterations' occupancies."""

from ..reading import named


def read(inputs):
    occ = sum(s[3].get("occupancy", 0)
              for s in named(inputs, "serving.decode_iter"))
    if not occ:
        return None
    return 100.0 * (1.0 - inputs["counters"]["generated_tokens"] / occ)
