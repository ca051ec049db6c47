"""BSBM's e-commerce data set, by the rules of the Berlin SPARQL Benchmark.

Bizer and Schultz's BSBM (specification V3.1, data generation) scales one
schema by the number of products ``n``.  Products sit in a product-type
hierarchy of depth ``round(log10(n) / 2) + 1`` below one root, which has
``2 * round(log10(n))`` children, every other type 8; each product is an
instance of one leaf type.  Each type owns a set of product features.  A
product has a label, a comment, a producer (about 50 products each), its
features, textual properties 1-3 and numeric properties 1-3, and textual
and numeric properties 4 and 5 on only part of the products.  Vendors
(about 2000 offers each) make 20 offers a product, each with a price,
a validity interval, delivery days and a web page.  Persons (about 20
reviews each) write 10 reviews a product, each with a date, a title, a
text and ratings 1-4, each rating on only part of the reviews.  Producers,
vendors, persons and rating sites have a country; every entity has a
publisher and a date.

The configuration's ``profile`` holds every rate and range used here; its
``assumed`` lists where they are this file's own choice.  The draws are
numpy's from ``data_seed``, not the Java generator's: the same rules, not
the same triples.  The graph does not depend on the run's seed, so every
run serves the same operators with the same compiled shapes; the run's seed
draws the traffic.

Literals are nodes, one per distinct value: a label, comment, title, text,
name, mailbox, home page or web page is one node per entity, a date, an
integer or a price one node per value.  ``rdf:type`` is materialised for a
product's leaf type and each of its ancestors, so a query on an inner type
needs no inference.

Kinds the traffic draws from: ``product`` (in id order), ``product_type``
(every type but the root, upper levels first) and ``product_feature`` /
``product_feature_b`` (the features at even / odd positions of the list of
all features, upper levels first); ``offer`` and ``review`` for the tests.
A feature of the root or of a type one level below it is carried by every
product below its type, so the first ranks of both feature kinds are
features many products share.  The two kinds never hold the same feature:
a query that binds one slot from each never repeats a constant, which would
give it another template.
"""
from __future__ import annotations

import numpy as np

from data import Dataset

TEXTUAL = tuple(f"productPropertyTextual{k}" for k in range(1, 6))
NUMERIC = tuple(f"productPropertyNumeric{k}" for k in range(1, 6))
RATINGS = tuple(f"rating{k}" for k in range(1, 5))
LABELS = (
    "type", "label", "comment", "subClassOf", "publisher", "date", "homepage",
    "country", "producer", "productFeature", *TEXTUAL, *NUMERIC, "product",
    "vendor", "price", "validFrom", "validTo", "deliveryDays", "offerWebpage",
    "reviewFor", "reviewer", "reviewDate", "title", "text", *RATINGS, "name",
    "mbox_sha1sum",
)
CLASSES = ("Product", "ProductFeature", "Producer", "Vendor", "Offer",
           "Person", "Review", "RatingSite")


def hierarchy(n_products: int, branching: int) -> list:
    """Types per level, root first: ``[1, 2 * round(log10 n), ...]``."""
    lg = np.log10(max(n_products, 1))
    depth = int(round(lg / 2)) + 1
    sizes = [1, 2 * int(round(lg))]
    while len(sizes) <= depth:
        sizes.append(sizes[-1] * branching)
    return sizes


def _spans(counts: np.ndarray):
    """(owner, local index) of ``sum(counts)`` items dealt to owners in
    order."""
    counts = np.asarray(counts, np.int64)
    start = np.cumsum(counts) - counts
    owner = np.repeat(np.arange(len(counts)), counts)
    return owner, np.arange(owner.size) - start[owner]


def _between(gen, lo_hi, size) -> np.ndarray:
    """Uniform whole numbers in the closed range ``lo_hi``."""
    lo, hi = lo_hi
    return gen.integers(lo, hi + 1, size)


def generate(cfg: dict, seed: int) -> Dataset:
    del seed  # the data set is BSBM(products, data_seed); see the module doc
    pr = cfg["profile"]
    n = cfg["products"]
    gen = np.random.default_rng(pr["data_seed"])

    # ---- the product-type hierarchy and its features ------------------ #
    levels = hierarchy(n, pr["type_branching"])
    n_types = sum(levels)
    level_of = np.repeat(np.arange(len(levels)), levels)
    level0 = np.cumsum([0] + levels[:-1])  # first type id of each level
    parent = np.full(n_types, -1, np.int64)
    for k in range(1, len(levels)):
        per = levels[k] // levels[k - 1]
        parent[level0[k]:level0[k] + levels[k]] = (
            level0[k - 1] + np.arange(levels[k]) // per)
    anc = np.zeros((n_types, len(levels)), np.int64)  # ancestor at each level
    for t in range(n_types):
        anc[t, level_of[t]] = t
        for k in range(level_of[t] - 1, -1, -1):
            anc[t, k] = parent[anc[t, k + 1]]
    n_feat_t = _between(gen, pr["features_per_type"], n_types)
    feat0 = np.cumsum(n_feat_t) - n_feat_t  # features in type (level) order
    n_feat = int(n_feat_t.sum())

    # ---- entity counts -------------------------------------------------- #
    n_producer = max(1, -(-n // pr["products_per_producer"]))
    n_offer = n * pr["offers_per_product"]
    n_vendor = max(1, -(-n_offer // pr["offers_per_vendor"]))
    n_review = n * pr["reviews_per_product"]
    n_person = max(1, -(-n_review // pr["reviews_per_person"]))
    n_site = max(1, -(-n_person // pr["persons_per_rating_site"]))
    countries = pr["countries"]

    # ---- draws ---------------------------------------------------------- #
    leaves = level0[-1] + np.arange(levels[-1])
    p_leaf = leaves[gen.integers(0, levels[-1], n)]
    p_producer = gen.integers(0, n_producer, n)
    # features: all of the upper levels' types, part of the lower levels'
    f_owner, f_local, f_keep = [], [], []
    for k in range(len(levels)):
        t = anc[p_leaf, k]
        owner, local = _spans(n_feat_t[t])
        f_owner.append(owner)
        f_local.append(feat0[t[owner]] + local)
        f_keep.append(np.ones(owner.size, bool) if k < pr["upper_levels"]
                      else gen.random(owner.size) < pr["lower_feature_share"])
    f_owner = np.concatenate(f_owner)[np.concatenate(f_keep)]
    f_feat = np.concatenate(f_local)[np.concatenate(f_keep)]
    opt = pr["optional_property_share"]
    has_text = [np.ones(n, bool)] * 3 + [gen.random(n) < opt for _ in range(2)]
    has_num = [np.ones(n, bool)] * 3 + [gen.random(n) < opt for _ in range(2)]
    num_val = [_between(gen, pr["numeric_range"], int(m.sum())) for m in has_num]
    o_vendor = gen.integers(0, n_vendor, n_offer)
    o_price = _between(gen, pr["price_cents"], n_offer)
    o_from = gen.integers(0, pr["days"], n_offer)
    o_to = o_from + _between(gen, pr["valid_days"], n_offer)
    o_delivery = _between(gen, pr["delivery_days"], n_offer)
    r_person = gen.integers(0, n_person, n_review)
    r_site = gen.integers(0, n_site, n_review)
    r_rated = [gen.random(n_review) < pr["rating_share"] for _ in RATINGS]
    r_rating = [_between(gen, pr["rating_range"], int(m.sum())) for m in r_rated]
    c_producer = gen.integers(0, len(countries), n_producer)
    c_vendor = gen.integers(0, len(countries), n_vendor)
    c_person = gen.integers(0, len(countries), n_person)
    c_site = gen.integers(0, len(countries), n_site)

    # integer, price and date literals: one node per distinct value
    ints = np.unique(np.concatenate(num_val + r_rating + [o_delivery]))
    prices = np.unique(o_price)
    n_days = int(o_to.max()) + 1

    # ---- node ids, kind by kind ----------------------------------------- #
    labelled = {"type": n_types, "feature": n_feat, "producer": n_producer,
                "product": n, "vendor": n_vendor, "site": n_site}
    n_labelled = sum(labelled.values())
    sizes = {
        "class": len(CLASSES), "country": len(countries),
        **labelled,
        "offer": n_offer, "person": n_person, "review": n_review,
        "label": n_labelled, "comment": n_labelled,
        "homepage": n_producer + n_vendor + n_site,
        "textual": int(sum(m.sum() for m in has_text)),
        "int": len(ints), "price": len(prices), "day": n_days,
        "webpage": n_offer, "title": n_review, "text": n_review,
        "name": n_person, "mbox": n_person,
    }
    off, acc = {}, 0
    for k, c in sizes.items():
        off[k] = acc
        acc += c
    n_nodes = acc
    ids = {k: off[k] + np.arange(c) for k, c in sizes.items()}

    lab = {name: i for i, name in enumerate(LABELS)}
    cls = {name: off["class"] + i for i, name in enumerate(CLASSES)}
    parts = []

    def emit(label, s, o):
        s = np.asarray(s, np.int64)
        parts.append(np.stack([s, np.full(s.size, lab[label], np.int64),
                               np.broadcast_to(np.asarray(o, np.int64), s.shape)],
                              1))

    def day(d):
        return off["day"] + d

    # every labelled entity: a label and a comment of its own
    at = 0
    for k in labelled:
        emit("label", ids[k], off["label"] + at + np.arange(sizes[k]))
        emit("comment", ids[k], off["comment"] + at + np.arange(sizes[k]))
        at += sizes[k]

    types, feats, prod = ids["type"], ids["feature"], ids["product"]
    producers, vendors, sites = ids["producer"], ids["vendor"], ids["site"]
    offers, persons, reviews = ids["offer"], ids["person"], ids["review"]

    # the hierarchy and the features
    emit("subClassOf", types[1:], types[parent[1:]])
    emit("publisher", types, sites[0])
    emit("date", types, day(gen.integers(0, pr["days"], n_types)))
    emit("type", feats, cls["ProductFeature"])
    emit("publisher", feats, sites[0])
    emit("date", feats, day(gen.integers(0, pr["days"], n_feat)))

    # producers, vendors and rating sites
    home = off["homepage"]
    for k, c, name in ((producers, c_producer, "Producer"),
                       (vendors, c_vendor, "Vendor"),
                       (sites, c_site, "RatingSite")):
        emit("type", k, cls[name])
        emit("homepage", k, home + np.arange(k.size))
        home += k.size
        emit("country", k, off["country"] + c)
        emit("publisher", k, k)
        emit("date", k, day(gen.integers(0, pr["days"], k.size)))

    # products
    emit("type", prod, cls["Product"])
    for k in range(len(levels)):
        emit("type", prod, types[anc[p_leaf, k]])
    emit("producer", prod, producers[p_producer])
    emit("publisher", prod, producers[p_producer])
    emit("date", prod, day(gen.integers(0, pr["days"], n)))
    emit("productFeature", prod[f_owner], feats[f_feat])
    nxt = off["textual"]
    for name, m in zip(TEXTUAL, has_text):
        c = int(m.sum())
        emit(name, prod[m], nxt + np.arange(c))
        nxt += c
    for name, m, v in zip(NUMERIC, has_num, num_val):
        emit(name, prod[m], off["int"] + np.searchsorted(ints, v))

    # offers: 20 a product, in product order
    o_prod = np.repeat(np.arange(n), pr["offers_per_product"])
    emit("type", offers, cls["Offer"])
    emit("product", offers, prod[o_prod])
    emit("vendor", offers, vendors[o_vendor])
    emit("publisher", offers, vendors[o_vendor])
    emit("price", offers, off["price"] + np.searchsorted(prices, o_price))
    emit("validFrom", offers, day(o_from))
    emit("validTo", offers, day(o_to))
    emit("deliveryDays", offers, off["int"] + np.searchsorted(ints, o_delivery))
    emit("offerWebpage", offers, ids["webpage"])
    emit("date", offers, day(o_from))

    # persons and their reviews: 10 a product, in product order
    emit("type", persons, cls["Person"])
    emit("name", persons, ids["name"])
    emit("mbox_sha1sum", persons, ids["mbox"])
    emit("country", persons, off["country"] + c_person)
    emit("publisher", persons, sites[gen.integers(0, n_site, n_person)])
    emit("date", persons, day(gen.integers(0, pr["days"], n_person)))
    r_prod = np.repeat(np.arange(n), pr["reviews_per_product"])
    r_day = gen.integers(0, pr["days"], n_review)
    emit("type", reviews, cls["Review"])
    emit("reviewFor", reviews, prod[r_prod])
    emit("reviewer", reviews, persons[r_person])
    emit("reviewDate", reviews, day(r_day))
    emit("title", reviews, ids["title"])
    emit("text", reviews, ids["text"])
    for name, m, v in zip(RATINGS, r_rated, r_rating):
        emit(name, reviews[m], off["int"] + np.searchsorted(ints, v))
    emit("publisher", reviews, sites[r_site])
    emit("date", reviews, day(r_day))

    triples = np.concatenate(parts).astype(np.int32)

    # ---- names ---------------------------------------------------------- #
    def seq(prefix, count):
        return [f"{prefix}{i}" for i in range(count)]

    names = (
        [f"class:{c}" for c in CLASSES]
        + [f"country:{c}" for c in countries]
        + seq("ProductType", n_types) + seq("ProductFeature", n_feat)
        + seq("Producer", n_producer) + seq("Product", n)
        + seq("Vendor", n_vendor) + seq("RatingSite", n_site)
        + seq("Offer", n_offer) + seq("Reviewer", n_person)
        + seq("Review", n_review)
        + seq("label:", n_labelled) + seq("comment:", n_labelled)
        + seq("homepage:", sizes["homepage"]) + seq("textual:", sizes["textual"])
        + [f"int:{v}" for v in ints.tolist()]
        + [f"price:{v // 100}.{v % 100:02d}" for v in prices.tolist()]
        + seq("day:", n_days) + seq("webpage:", n_offer)
        + seq("title:", n_review) + seq("text:", n_review)
        + seq("name:", n_person) + seq("mbox:", n_person)
    )
    assert len(names) == n_nodes
    # features in type order are already upper levels first
    kinds = {
        "product": prod, "product_type": types[1:],
        "product_feature": feats[0::2], "product_feature_b": feats[1::2],
        "offer": offers, "review": reviews,
    }
    return Dataset(triples, names, list(LABELS), kinds)
