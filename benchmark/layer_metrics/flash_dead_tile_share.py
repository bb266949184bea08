"""Of the tile pairs of the flash forward's grid under a mask form (block
diffusion's three-part mask), the share the kernels skip: neither computed
nor, through the index maps, copied.  ``paddle_tpu_flash_tile_pairs_total
{pass="fwd", state="dead"}`` over all three states, which the program counts
at lowering from the call's own blocks (one head's grid a lowering, so the
share is the same however many lowerings a step has).  68.75 at 8192-token
documents in blocks of 1024: of 256 tile pairs a head 176 are dead, 56 run
mask-free and 24 run the mask.  It falls if the skipping is lost (a mask
form lowered as a dense bias reads nothing here; every tile live reads 0).
Nothing to read where the program has no such counter or counted nothing
(a program without the mask form, or a commit before it)."""


def read(inputs):
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get("paddle_tpu_flash_tile_pairs_total")
    if fam is None:
        return None
    pairs = {}
    for labels, cell in fam.series():
        if labels.get("pass") == "fwd":
            state = labels.get("state")
            pairs[state] = pairs.get(state, 0.0) + cell.get()
    total = sum(pairs.values())
    if not total:
        return None
    return 100.0 * pairs.get("dead", 0.0) / total
