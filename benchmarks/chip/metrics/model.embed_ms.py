"""model.embed_ms: device time per step of the ops under the
``model/embed`` scope: the token embedding's gather and, in the backward
pass, its scatter-add, on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_ms(ctx, scopes.in_sublayer("embed"))
