"""Device milliseconds per traced step under the tag ``kda``: everything of
``models.transformer.kda_attention``, forward, backward and recomputed (the
fused input projection, the three convolutions, the gates' up-projections and
``kda_gate``, ``kda_scan`` and its grad op, the head norm, the output gate and
the output projection): what a gated delta-rule layer costs beside the
softmax layer it stands in for.  The tag is a ``framework.name_scope`` of the
program, which follows the op's own scope in the trace (``pt.fwd/mul/kda``,
``pt.bwd/kda_scan_grad/kda``) and which grad ops inherit.  Nothing to read
where the trace holds no such tag (a program without the sublayer, or a
commit before it)."""

from .. import tag_scopes


def read(inputs):
    return tag_scopes.train_ms_under(inputs, ("kda",))
