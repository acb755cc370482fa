"""Device ms per round of the server's backward (the transposed ops of the
round's ``sfpl.server`` scope) and its optimizer step
(``sfpl.server_opt``), averaged over the chips; ``None`` without the
round's scope map (``scopes.of_program``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("server", "bwd"), ("server_opt", None))
