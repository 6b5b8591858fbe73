"""chip_smoke.py's own parts, small, on CPU: the seeded generator, the
HTTP loader, the query list and the numpy reference must agree with a
real server; the device assertion must refuse a CPU server; and the
compile-cache function must leave a cache dir placed from outside alone.
"""

import os

import jax
import pytest

import chip_smoke as cs
import pilosa_tpu
from pilosa_tpu.cli.main import configure_compile_cache
from pilosa_tpu.testing import ClusterHarness


def test_smoke_parts_agree_with_a_served_index_small():
    with ClusterHarness(1, in_memory=True) as c:
        uri = c[0].node.uri
        http_ = cs.Http(uri)
        info = http_.call("GET", "/info")
        data = cs.Data(seed=3, shards=3, shard_width=info["shardWidth"])
        ref = cs.Reference(data)
        queries = cs.read_queries(ref)
        cs.create_schema(http_)
        assert set(cs.load(uri, data)) == {"f", "g", "h", "k", "v"}
        # a CPU server tallies its GroupBy and filtered TopN with the
        # XLA program, and the smoke's counter check says so; every cold
        # dispatch spans the suite's 8-device mesh, and its check that
        assert len(info["devices"]) == 8
        device = {"platform": "cpu", "kind": "cpu", "count": 8}
        before = cs.tally_counts(http_)
        cs.run_queries(http_, queries, cold=True, device=device)
        kernel, xla, inplace, assembled, mesh, compacted = (
            a - b for a, b in zip(cs.tally_counts(http_), before))
        # 3 shards are one extent (and the mesh stages one array anyway)
        assert kernel == 0 and xla >= 1 and (inplace, assembled) == (0, 0)
        assert mesh == 0  # a CPU mesh runs no kernel
        assert compacted == 0  # nor cuts a tally to its named rows
        # on a TPU the aggregate over k's 10 named rows is cut, at a
        # 4 096-word tile, and the one over 30 resident rows is not
        for device_ in (dict(device, platform="tpu", count=1),
                        dict(device, platform="tpu", count=4)):
            cs.check_cut("group_by_sum_cut", 1, 4096, device_)
            cs.check_cut("group_by_sum", 0, 16384, device_)
            for family, cut, words in (("group_by_sum_cut", 0, 0),
                                       ("group_by_sum_cut", 1, 2048),
                                       ("group_by_sum", 1, 4096)):
                with pytest.raises(AssertionError, match="named rows"):
                    cs.check_cut(family, cut, words, device_)
        cs.check_cut("group_by_sum_cut", 0, 0, device)
        # four TPU chips: the aggregate's group tally is the kernel on the
        # mesh and nothing else; the count-only cross stays XLA's there
        four = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
        cs.check_tally_program("group_by_sum", 3, 0, four, mesh=3)
        for kernel_, xla_, mesh_ in ((0, 3, 0), (3, 1, 3), (3, 0, 2)):
            with pytest.raises(AssertionError, match="on the mesh"):
                cs.check_tally_program("group_by_sum", kernel_, xla_, four,
                                       mesh=mesh_)
        cs.check_tally_program("group_by", 0, 1, four)
        with pytest.raises(AssertionError, match="on the mesh"):
            cs.check_tally_program("group_by", 1, 1, four, mesh=1)
        one_chip = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        with pytest.raises(AssertionError, match="cross tallies"):
            cs.check_tally_program("group_by", kernel, xla, one_chip)
        # one chip: the kernel, over the extents as they lie
        cs.check_tally_program("group_by", 1, 0, one_chip, assembled=0)
        with pytest.raises(AssertionError, match="concatenated"):
            cs.check_tally_program("group_by", 1, 0, one_chip, assembled=2)
        cs.check_tally_program("group_by", 0, 1, device, assembled=2)
        with pytest.raises(AssertionError, match=r"mesh.devices \[8\]"):
            cs.check_placement("count_intersect", [8], dict(device, count=4))
        cs.run_queries(http_, queries, cold=False)
        readback = cs.write_then_read(http_, data, ref)
        assert [n for _, n in readback] == [
            len(ref.row("f", 1)), len(ref.row("f", 0)),
        ]
        # the reference moved with the writes: the query list built from
        # it again still agrees
        cs.run_queries(http_, cs.read_queries(ref), cold=False)
        http_.close()


def test_a_wrong_answer_fails_the_smoke():
    with ClusterHarness(1, in_memory=True) as c:
        http_ = cs.Http(c[0].node.uri)
        cs.create_schema(http_)
        with pytest.raises(AssertionError, match="want 1"):
            cs.run_queries(
                http_, [("count", "Count(Row(f=0))", 1, 0)], cold=True
            )
        http_.close()


def test_device_assertion_refuses_a_cpu_server():
    with ClusterHarness(1, in_memory=True) as c:
        http_ = cs.Http(c[0].node.uri)
        info = http_.call("GET", "/info")
        http_.close()
    assert info["devices"] and info["devices"][0]["platform"] == "cpu"
    assert info["hbmBudgetBytes"] > 0
    with pytest.raises(RuntimeError, match="not on a TPU"):
        cs.check_device(info)
    with pytest.raises(RuntimeError, match="not on a TPU"):
        cs.check_device({"shardWidth": 1 << 20})  # a server that says nothing
    tpu = dict(id=0, platform="tpu", deviceKind="TPU v5 lite",
               bytesInUse=0, bytesLimit=1)
    assert cs.check_device({"devices": [tpu]}) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }
    with pytest.raises(RuntimeError, match="not on a TPU"):
        cs.check_device({"devices": [tpu, info["devices"][0]]})


@pytest.fixture
def restore_jax_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_dir_is_placed_from_outside(
    monkeypatch, restore_jax_cache_config
):
    # set: the code sets no directory of its own (jax reads the variable
    # itself at import; the config value here is whatever it was)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    jax.config.update("jax_compilation_cache_dir", "/some/dir")
    assert configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/some/dir"
    # unset: the fixed <checkout>/.jax_cache beside the package
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(pilosa_tpu.__file__))
    assert configure_compile_cache() == os.path.join(checkout, ".jax_cache")
    assert configure_compile_cache() == os.path.join(checkout, ".jax_cache")
