"""Device milliseconds per traced step under the tag ``mla_proj``: everything
of ``models.transformer.latent_attention`` outside the flash op, forward and
backward (the fused down-projection, the two latent norms, the two
up-projections, the head splits, the rotary slice, the concatenation of Q
and the broadcast and concatenation of K, the output projection), the
multi-token-prediction module's block among it: what the low-rank path and
the shared rotary key cost beside the kernel.  The tag is a
``framework.name_scope`` of the program, which follows the op's own scope in
the trace (``pt.fwd/mul/mla_proj``, ``pt.bwd/mul_grad/mtp.mla_proj``).
Nothing to read where the trace holds no such tag."""

from .. import tag_scopes

#: the tags as the program nests them (``mtp.`` in front inside the module)
TAGS = ("mtp.mla_proj", "mla_proj")


def read(inputs):
    return tag_scopes.train_ms_under(inputs, TAGS)
