"""Where a compiled dispatch ran (ISSUE 28): every `exec.dispatch` span
says how many devices its program's operands span (`mesh.devices`) and,
above one, over which mesh (`mesh.axes`); `/debug/vars` carries the active
mesh's size as the gauge `mesh.devices`. Read from the arrays' sharding,
so it holds for the Count plan, the BSI stream and the GroupBy tally alike.
"""

import jax
import numpy as np
import pytest

from pilosa_tpu.exec import plan as planmod
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.testing import ClusterHarness

from test_layer_spans import _by_name, _get, _post, _seed

QUERIES = [
    ("Count(Union(Row(f=0),Row(g=2)))", "stacked"),
    ("GroupBy(Rows(f),Rows(g))", "groupby"),
    ("Sum(field=v)", "bsi"),
]


@pytest.fixture(params=[(4, "shards=2,cols=2"), (8, "shards=4,cols=2"), (0, None)],
                ids=["mesh4", "mesh8", "one-device"])
def placed(request):
    """A served node whose active mesh spans 4 or 8 of the suite's virtual
    devices, or none; (server, devices a dispatch spans, mesh axes)."""
    n, axes = request.param
    old = pmesh.active_mesh()
    with ClusterHarness(1, in_memory=True) as c:
        pmesh.set_active_mesh(pmesh.make_mesh(jax.devices()[:n]) if n else None)
        try:
            _seed(c[0].api)
            c[0].api.create_field("ls", "v", {"type": "int", "min": 0, "max": 500})
            c[0].api.import_values(
                "ls", "v", list(range(0, 400, 4)), list(range(100)))
            yield c[0], n, axes
        finally:
            pmesh.set_active_mesh(old)


def test_every_dispatch_of_every_family_says_where_it_ran(placed):
    srv, n, axes = placed
    folded = _get(srv, "/debug/vars")["mesh.local_shards"]  # process-wide
    for text, family in QUERIES:
        out, _, _ = _post(srv, text)
        dispatches = _by_name(out["profile"]["roots"][0])["exec.dispatch"]
        assert family in {d["tags"]["plan.family"] for d in dispatches}
        for d in dispatches:
            assert d["tags"]["mesh.devices"] == (n or 1), (text, d["tags"])
            assert d["tags"].get("mesh.axes") == axes, (text, d["tags"])
    gauges = _get(srv, "/debug/vars")
    assert gauges["mesh.devices"] == n
    # the multi-node fold's gauges are not this: a single node never folds
    assert gauges["mesh.group_size"] == 0
    assert gauges["mesh.local_shards"] == folded


def test_placement_is_read_from_the_first_device_array():
    mesh = pmesh.make_mesh(jax.devices()[:4])
    sharded = jax.device_put(
        np.zeros((4, 8), np.uint32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("shards", "cols")),
    )
    single = jax.device_put(np.zeros((4, 8), np.uint32), jax.devices()[0])
    assert planmod._placement([sharded, single]) == (4, "shards=2,cols=2")
    assert planmod._placement((single,)) == (1, "")
    assert planmod._placement({"host": np.zeros(3), "dev": (sharded,)}) == (
        4, "shards=2,cols=2")
    assert planmod._placement([]) == (1, "")  # a plan of constants alone
