"""model.mlp_ms: device time per step of the ops under the ``model/mlp``
scope: the gated MLP (or the mixture of experts), forward, backward and
recomputation, on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_ms(ctx, scopes.in_sublayer("mlp"))
