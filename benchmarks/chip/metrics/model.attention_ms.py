"""model.attention_ms: device time per step of the ops under the
``model/attention`` scope: attention with its q/k/v/o projections,
forward, backward and recomputation, on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_ms(ctx, scopes.in_sublayer("attention"))
