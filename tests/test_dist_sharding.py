"""Fast single-device unit tests for repro.dist.sharding: tensor-parallel
priority, FSDP dim selection, replicated scalars, absent mesh axes."""
import jax
import pytest

from repro.dist.sharding import spec_for, tree_shardings
from repro.launch.mesh import make_mesh

P = jax.sharding.PartitionSpec

pytestmark = pytest.mark.unit


def fake_mesh(names, shape):
    class _Devices:
        pass

    class _Mesh:
        axis_names = tuple(names)
        devices = _Devices()

    _Mesh.devices.shape = tuple(shape)
    return _Mesh()


@pytest.fixture
def mesh16():
    return fake_mesh(("data", "model"), (16, 16))


# -- FSDP dim selection -------------------------------------------------------

def test_fsdp_picks_largest_divisible_dim(mesh16):
    # mlp wins the model axis by priority; FSDP then takes embed (largest
    # remaining divisible), not the smaller mlp leftovers
    assert spec_for(("embed", "mlp"), (4096, 11008), mesh16) == \
        P("data", "model")
    # wo: ("mlp", "embed") -- same pair, transposed order
    assert spec_for(("mlp", "embed"), (11008, 4096), mesh16) == \
        P("model", "data")


def test_fsdp_skips_indivisible_and_layers(mesh16):
    # embed 100 not divisible by 16: nothing to FSDP, model takes head_dim
    spec = spec_for(("embed", "head_dim"), (100, 128), mesh16)
    assert spec == P(None, "model")
    # the scan-stacked "layers" dim is never sharded even when divisible
    spec = spec_for(("layers", "embed"), (32, 4096), mesh16)
    assert spec == P(None, "data")


def test_fsdp_off_replicates_data_dims(mesh16):
    assert spec_for(("embed", "mlp"), (4096, 11008), mesh16, fsdp=False) == \
        P(None, "model")


def test_fsdp_never_doubles_the_model_dim(mesh16):
    # one dim, divisible by both axes: model wins, FSDP must not re-shard it
    assert spec_for(("mlp",), (4096,), mesh16) == P("model")


# -- replicated scalars and unnamed dims --------------------------------------

def test_replicated_scalars_and_unnamed(mesh16):
    assert spec_for((), (), mesh16) == P()
    assert spec_for((None,), (7,), mesh16) == P(None)
    # unnamed dims stay replicated even when divisible
    assert spec_for((None, None), (64, 64), mesh16) == P(None, None)


# -- axis names absent from the mesh ------------------------------------------

def test_mesh_without_model_axis():
    m = fake_mesh(("data",), (8,))
    # no model axis: tensor dims fall back to replication, FSDP still works
    assert spec_for(("vocab", "embed"), (50304, 4096), m) == P("data", None)
    assert spec_for(("vocab", "embed"), (50304, 4096), m, fsdp=False) == \
        P(None, None)


def test_mesh_without_data_axes():
    m = fake_mesh(("model",), (4,))
    # no DP fabric: batch and FSDP have nowhere to go
    assert spec_for(("batch", None), (8, 128), m) == P(None, None)
    assert spec_for(("embed", "mlp"), (4096, 11008), m) == P(None, "model")


def test_unknown_logical_axis_is_fsdp_eligible(mesh16):
    # names outside the TP priority list replicate on model but may FSDP
    spec = spec_for(("state", "embed"), (8192, 4096), mesh16)
    assert spec == P("data", None)


# -- batch + pod/data composition ---------------------------------------------

def test_batch_maps_to_all_dp_axes():
    m = fake_mesh(("pod", "data", "model"), (2, 16, 16))
    assert spec_for(("batch", None), (64, 128), m, fsdp=False) == \
        P(("pod", "data"), None)
    # batch not divisible by pod*data: replicated
    assert spec_for(("batch", None), (16, 128), m, fsdp=False) == \
        P(None, None)


# -- tree_shardings -----------------------------------------------------------

def test_tree_shardings_structure_and_cache_pairs():
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"w": jax.ShapeDtypeStruct((64, 128), jax.numpy.float32),
              "scale": jax.ShapeDtypeStruct((64,), jax.numpy.float32),
              "cache": (jax.ShapeDtypeStruct((2, 8, 4, 16), jax.numpy.float32),
                        jax.ShapeDtypeStruct((2, 8, 4, 16), jax.numpy.float32))}
    axes = {"w": ("embed", "mlp"), "scale": ("embed",),
            "cache": (("batch", None, "kv_heads", "head_dim"),
                      ("batch", None, "kv_heads", "head_dim"))}
    sh = tree_shardings(axes, params, mesh)
    assert sh["w"].spec == P("data", "model")
    assert sh["scale"].spec == P("data")
    # a (k, v) tuple of axis-tuples is an interior node, not one leaf
    assert isinstance(sh["cache"], tuple) and len(sh["cache"]) == 2
    assert sh["cache"][0].spec == P("data", None, "model", None)
    for s in jax.tree.leaves(sh):
        assert isinstance(s, jax.sharding.NamedSharding)
