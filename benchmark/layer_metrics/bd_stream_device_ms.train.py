"""Device milliseconds per traced step under the tag ``bd_stream``: what the
block-diffusion objective adds to the step outside attention, forward,
backward and recomputed: joining the noisy and the clean copy into one
stream, the reshapes that give each copy its own positions round ``rope``,
taking the noisy half for the head, and weighting the loss
(``models.transformer.build_sdar_pretrain``).  The mask itself is inside the
flash ops (``attention_device_ms.train``, ``flash_roofline``).  The tag is a
``framework.name_scope`` of the program, which follows the op's own scope in
the trace (``pt.fwd/concat/bd_stream``; inside the attention's own tag it
reads ``pt.fwd/reshape2/attn.bd_stream``) and which grad ops inherit.
Nothing to read where the trace holds no such tag (a program without the
objective, or a commit before it)."""

from .. import tag_scopes


def read(inputs):
    return tag_scopes.train_ms_under(inputs, ("attn.bd_stream", "bd_stream"))
