"""The reference against the program on OPTIONAL and UNION.

A small graph by hand in which some optional data is present and some is
absent: widgets w1-w3 and a gadget g1; w1 and g1 are red, w2 blue; w1 and
w3 have makers; vendors sell w1-w3, each with a partner maker; reviews of
w1, w2 and g1, of which r2's has no rating.  For each query the survivors
the program serves (``GraphDB``, as ``run.py`` builds it) must equal
``reference.survivors``, triple for triple.
"""
import numpy as np
import pytest

import check
import reference
import run
from data import Dataset

TRIPLES = """
w1 type Widget . w2 type Widget . w3 type Widget . g1 type Gadget .
w1 feature Red . w2 feature Blue . g1 feature Red .
w1 maker m1 . w3 maker m2 .
v1 sells w1 . v1 partner m1 . v2 sells w3 . v2 partner m9 .
v3 sells w2 . v3 partner m1 .
r1 reviewFor w1 . r2 reviewFor w2 . r3 reviewFor g1 .
r1 rating five . r3 rating two
"""

QUERIES = {
    "optional_constant":
        "{ ?p type Widget . ?p maker ?m } OPTIONAL { ?p feature Red }",
    "nested_optional":
        "{ ?p type Widget } OPTIONAL { { ?r reviewFor ?p } OPTIONAL { ?r rating ?x } }",
    # ?m is in both optional siblings and not in the mandatory part
    "siblings_not_well_designed":
        "{ ?p type Widget } OPTIONAL { ?p maker ?m } "
        "OPTIONAL { ?s sells ?p . ?s partner ?m }",
    "top_union":
        "{ ?p feature Red . ?p type ?t } UNION { ?r reviewFor ?p . ?r rating ?x }",
    "union_in_optional":
        "{ ?p type Widget } OPTIONAL { { ?p feature ?f } UNION { ?r reviewFor ?p } }",
}


def dataset() -> Dataset:
    rows = [t.split() for t in TRIPLES.split(" .")]
    nodes = sorted({x for s, _, o in rows for x in (s, o)})
    labels = sorted({p for _, p, _ in rows})
    ni = {n: i for i, n in enumerate(nodes)}
    li = {n: i for i, n in enumerate(labels)}
    t = np.array([[ni[s], li[p], ni[o]] for s, p, o in rows], np.int32)
    return Dataset(t, nodes, labels, {})


@pytest.fixture(scope="module")
def graph():
    ds = dataset()
    return ds, reference.RefGraph(ds.triples, ds.n_nodes, ds.label_names,
                                  ds.node_index())


@pytest.fixture(scope="module", params=["auto", "dense"])
def served(request, graph):
    """Each query's answer, served by one ``execute_many`` call; ``auto``
    picks the sparse engine here, as on LUBM."""
    ds, _ = graph
    _, cfg, _ = run.cell(run.benchmark(), run.benchmark()["workloads"][0]["name"])
    db = run.build_db({**cfg, "engine": request.param}, ds)
    return dict(zip(QUERIES, db.execute_many(list(QUERIES.values()))))


@pytest.mark.parametrize("name", QUERIES)
def test_program_serves_the_reference_survivors(name, graph, served):
    ds, ref = graph
    want = reference.survivors(QUERIES[name], ref)
    assert 0 < want.sum() < len(ds.triples)
    got = served[name]
    assert np.array_equal(check.rows(got.snapshot.triples, got.survivor_mask),
                          check.rows(ds.triples, want))


def test_the_control_differs_on_one_of_them(graph):
    _, ref = graph
    differ = [n for n, q in QUERIES.items()
              if not np.array_equal(reference.survivors(q, ref),
                                    reference.survivors(q, ref, stop_early=True))]
    assert differ
