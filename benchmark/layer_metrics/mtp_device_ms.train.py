"""Device milliseconds per traced step under the tag ``mtp``: the whole
multi-token-prediction module, forward and backward (its two norms, the
``[2 d, d]`` projection of the next token's embedding beside the main
model's output, its block with its own latent attention, flash op, router,
shared expert and held experts, its norm and the second pass of the shared
head).  How much of this cut's step the module is: one block of six here
where the deployment's is one of forty-one.  The tag is a
``framework.name_scope`` of the program (``pt.fwd/flash_attention/mtp``,
``pt.bwd/mul_grad/mtp.mla_proj``); what the module nests under it counts.
Nothing to read where the trace holds no such tag."""

from .. import tag_scopes

#: ``mtp`` alone and with each tag the program nests under it (the module's
#: block is never dense); tests/benchmark/test_joyai_cell.py holds the
#: lowered step's tags to this list
TAGS = ("mtp.mla_proj", "mtp.shared_expert", "mtp")


def read(inputs):
    return tag_scopes.train_ms_under(inputs, TAGS)
