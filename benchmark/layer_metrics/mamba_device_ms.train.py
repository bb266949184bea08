"""Device milliseconds per traced step under the tag ``mamba``: everything of
``models.transformer.mamba2_mixer``, forward, backward and recomputed (the
fused input projection, the biased convolution, ``ssd_scan`` and its grad op,
the gated norm and the output projection): what a Mamba-2 sublayer costs
beside the attention block it stands in for.  The tag is a
``framework.name_scope`` of the program, which follows the op's own scope in
the trace (``pt.fwd/mul/mamba``, ``pt.bwd/ssd_scan_grad/mamba``) and which
grad ops inherit.  Nothing to read where the trace holds no such tag (a
program without the sublayer, or a commit before it)."""

from .. import tag_scopes


def read(inputs):
    return tag_scopes.train_ms_under(inputs, ("mamba",))
