"""Mean ``occupancy`` of the ``serving.decode_iter`` spans over the slots."""

from ..reading import named


def read(inputs):
    occ = [s[3].get("occupancy") for s in named(inputs, "serving.decode_iter")]
    occ = [o for o in occ if o is not None]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / inputs["facts"]["slots"]
