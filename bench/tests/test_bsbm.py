"""The BSBM configuration: its generator's counts and type hierarchy, and
the program against the reference on every explore template.
"""
import collections

import numpy as np
import pytest

import check
import data
import reference
import run
import traffic

CELL = "bsbm.explore_closed"
# small enough for the dense engine's [n, n] adjacency per operator
SMALL = {"products": 12}


@pytest.fixture(scope="module")
def bsbm():
    _, cfg, mix = run.cell(run.benchmark(), CELL)
    return cfg, mix


@pytest.fixture(scope="module")
def tiny_ds(bsbm):
    cfg, _ = bsbm
    return data.generate({**cfg, **cfg["tiny"]}, 0)


def _by_label(ds, name):
    t = ds.triples
    return t[t[:, 1] == ds.label_names.index(name)]


def _per(ds, label, col, ids):
    """How many ``label`` triples each of ``ids`` holds in column ``col``."""
    rows = _by_label(ds, label)[:, col]
    return np.bincount(rows - ids[0], minlength=len(ids))[: len(ids)]


def test_counts_per_product_follow_the_specification(bsbm, tiny_ds):
    cfg, _ = bsbm
    ds, pr = tiny_ds, cfg["profile"]
    prod, offers, reviews = (ds.kinds[k] for k in ("product", "offer", "review"))
    assert len(prod) == cfg["tiny"]["products"]
    assert (_per(ds, "product", 2, prod) == pr["offers_per_product"]).all()
    assert (_per(ds, "reviewFor", 2, prod) == pr["reviews_per_product"]).all()
    assert len(offers) == 20 * len(prod) and len(reviews) == 10 * len(prod)
    # properties 1-3 on every product, 4 and 5 on part of them
    for kind in ("Textual", "Numeric"):
        for k in range(1, 6):
            has = _per(ds, f"productProperty{kind}{k}", 0, prod)
            assert has.max() == 1
            if k <= 3:
                assert has.all(), (kind, k)
            else:
                assert 0.3 < has.mean() < 0.7, (kind, k)
    # each rating on part of the reviews
    for k in range(1, 5):
        share = _per(ds, f"rating{k}", 0, reviews).mean()
        assert 0.5 < share < 0.9, k
    # every offer has one vendor, every review one reviewer
    assert (_per(ds, "vendor", 0, offers) == 1).all()
    assert (_per(ds, "reviewer", 0, reviews) == 1).all()


def test_graph_is_fixed_and_its_first_feature_ranks_are_widely_shared(
        bsbm, tiny_ds):
    cfg, _ = bsbm
    ds = tiny_ds
    again = data.generate({**cfg, **cfg["tiny"]}, 2**40 + 3)
    assert np.array_equal(ds.triples, again.triples)
    assert len(np.unique(ds.triples, axis=0)) == len(ds.triples)
    assert ds.triples.max() < ds.n_nodes == len(set(ds.node_names))
    assert len(ds.label_names) == 38
    # the two feature kinds are disjoint; their first ranks are the root's
    a, b = (set(ds.kinds[k].tolist()) for k in
            ("product_feature", "product_feature_b"))
    assert a.isdisjoint(b)
    pf = _by_label(ds, "productFeature")
    carried = collections.Counter(pf[:, 2].tolist())
    n = len(ds.kinds["product"])
    for kind in ("product_feature", "product_feature_b"):
        assert carried[int(ds.kinds[kind][0])] == n
    # every product is typed with its leaf type and each ancestor
    root = ds.kinds["product_type"][0] - 1
    typed = _by_label(ds, "type")
    assert (typed[:, 2] == root).sum() == n


@pytest.mark.parametrize("products, types", [(666, 55), (70812, 731)])
def test_type_hierarchy_matches_the_specification_where_its_levels_are_full(
        products, types):
    """The specification's type counts at 250K and 25M triples."""
    bsbm_gen = data.load_by_name("generators", "bsbm")
    assert sum(bsbm_gen.hierarchy(products, 8)) == types


@pytest.fixture(scope="module")
def small(bsbm):
    cfg, _ = bsbm
    ds = data.generate({**cfg, **SMALL}, 0)
    return ds, reference.RefGraph(ds.triples, ds.n_nodes, ds.label_names,
                                  ds.node_index())


def _texts(ds, template, b):
    return [traffic.fill(template["text"], {
        k: ds.node_names[ds.kinds[v["kind"]][j]]
        for k, v in template.get("slots", {}).items()}) for j in range(b)]


@pytest.mark.parametrize("engine", ["sparse", "dense"])
def test_program_serves_the_reference_on_every_template(engine, bsbm, small):
    cfg, mix = bsbm
    ds, ref = small
    db = run.build_db({**cfg, "engine": engine}, ds)
    nonempty = collections.Counter()
    for t in mix["templates"]:
        union = "UNION" in t["text"]
        for b in ([1] if union else [1, 2, 4]):
            texts = _texts(ds, t, b)
            for text, got in zip(texts, db.execute_many(texts)):
                assert got.engine.split(",")[0] == engine
                if not union:
                    assert got.batch == b
                want = reference.survivors(text, ref)
                assert np.array_equal(
                    check.rows(got.snapshot.triples, got.survivor_mask),
                    check.rows(ds.triples, want)), (t["name"], b, text)
                nonempty[t["name"]] += int(want.any())
    assert set(nonempty) == {t["name"] for t in mix["templates"]}
    assert all(nonempty.values()), nonempty

