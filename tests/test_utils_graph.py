"""Unit tests mirroring the reference's tests/unit suite (SURVEY §4):
dominators, disjoint_set, topo_sort, hash_combine, driver CLI."""

import pytest

from flexflow_tpu.utils.graph_algorithms import (DisjointSet, dominators,
                                                 hash_combine,
                                                 immediate_post_dominator,
                                                 post_dominators, topo_sort)

# diamond: a -> b, a -> c, b -> d, c -> d, d -> e
DIAMOND = {"a": ["b", "c"], "b": ["d"], "c": ["d"], "d": ["e"], "e": []}


class TestGraphAlgorithms:
    def test_topo_sort(self):
        order = topo_sort(DIAMOND)
        pos = {n: i for i, n in enumerate(order)}
        assert pos["a"] < pos["b"] < pos["d"] < pos["e"]
        assert pos["a"] < pos["c"] < pos["d"]

    def test_topo_sort_cycle(self):
        with pytest.raises(ValueError):
            topo_sort({"a": ["b"], "b": ["a"]})

    def test_dominators(self):
        dom = dominators(DIAMOND, "a")
        assert dom["d"] == {"a", "d"}  # neither b nor c dominates d
        assert dom["b"] == {"a", "b"}
        assert dom["e"] == {"a", "d", "e"}

    def test_post_dominators_find_bottleneck(self):
        pdom = post_dominators(DIAMOND, "e")
        # d post-dominates everything: it is the sequence-split point
        assert "d" in pdom["a"] and "d" in pdom["b"] and "d" in pdom["c"]
        assert immediate_post_dominator(DIAMOND, "b", "e") == "d"
        assert immediate_post_dominator(DIAMOND, "d", "e") == "e"

    def test_disjoint_set(self):
        ds = DisjointSet()
        ds.union(1, 2)
        ds.union(3, 4)
        assert ds.same(1, 2) and not ds.same(2, 3)
        ds.union(2, 3)
        assert ds.same(1, 4)

    def test_hash_combine_deterministic(self):
        h1 = hash_combine(hash_combine(0, "linear"), (64, 128))
        h2 = hash_combine(hash_combine(0, "linear"), (64, 128))
        h3 = hash_combine(hash_combine(0, "linear"), (64, 256))
        assert h1 == h2 != h3


class TestDriver:
    def test_launcher_parses_flags_and_runs_script(self, tmp_path, capsys,
                                                   monkeypatch):
        # a cache placed from outside: the launcher then sets none in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        script = tmp_path / "prog.py"
        script.write_text(
            "import sys\n"
            "from flexflow_tpu.driver import get_config\n"
            "cfg = get_config()\n"
            "print('B', cfg.batch_size, 'BUDGET', cfg.search_budget,"
            " 'REST', sys.argv[1:])\n")
        from flexflow_tpu.driver import main

        rc = main(["-b", "16", "--budget", "7", str(script), "--app-flag"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "B 16 BUDGET 7 REST ['--app-flag']" in out
