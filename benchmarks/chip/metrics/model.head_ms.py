"""model.head_ms: device time per step of the ops under the ``model/head``
scope: the tied output head and its chunked cross-entropy, forward,
backward and recomputation, on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_ms(ctx, scopes.in_sublayer("head"))
