"""LUBM universities with the shapes of the UBA generator's data profile.

Guo, Pan and Heflin's UBA (LUBM's data generator) makes each university
department by department from fixed ranges (the configuration's
``profile``): 15-25 departments; per department 7-10 full, 10-14
associate and 8-11 assistant professors and 5-7 lecturers, one full
professor heading it; 8-14 undergraduates and 3-4 graduate students per
faculty member; 1-2 courses and 1-2 graduate courses taught by each
faculty member; undergraduates take 2-4 courses, graduate students 1-3
graduate courses; one undergraduate in 5 and every graduate student has a
professor as advisor; one graduate student in 4-5 assists a course and one
in 3-4 a research group; 10-20 research groups; 15-20, 10-18, 5-10 and 0-5
publications per full, associate and assistant professor and lecturer, and
each graduate student co-authors 0-5 of the department's; every faculty
member holds three degrees and every graduate student an undergraduate
degree from a university drawn out of UBA's pool of 1000.

All 18 predicates of the data are written, literals as nodes: a literal
that repeats (a local name such as ``FullProfessor3``, the telephone
``xxx-xxx-xxxx``, a research interest, a class) is one node; an e-mail
address is one per person.  Per university that is about 134k triples and
33k nodes, as LUBM(10000)'s 1.38B triples over 329M nodes.

Every draw comes from the configuration's ``uba_seed``, as LUBM(N, seed)
names one fixed dataset: the graph does not depend on the run's seed, so
every run serves the same operators with the same compiled shapes.  The
run's seed draws the traffic.
"""
from __future__ import annotations

import numpy as np

from data import Dataset

LABELS = (
    "type", "name", "emailAddress", "telephone", "researchInterest",
    "subOrganizationOf", "worksFor", "headOf", "teacherOf",
    "undergraduateDegreeFrom", "mastersDegreeFrom", "doctoralDegreeFrom",
    "memberOf", "takesCourse", "advisor", "teachingAssistantOf",
    "publicationAuthor", "imports",
)
RANKS = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
         "Lecturer")
CLASSES = ("University", "Department") + RANKS + (
    "UndergraduateStudent", "GraduateStudent", "TeachingAssistant",
    "ResearchAssistant", "Course", "GraduateCourse", "ResearchGroup",
    "Publication")


def _spans(counts: np.ndarray):
    """(owner, local index, first id of each owner) of ``sum(counts)``
    items dealt to owners in order."""
    counts = np.asarray(counts, np.int64)
    start = np.cumsum(counts) - counts
    owner = np.repeat(np.arange(len(counts)), counts)
    return owner, np.arange(owner.size) - start[owner], start


def _between(gen, lo_hi, size) -> np.ndarray:
    """Uniform whole numbers in the closed range ``lo_hi``."""
    lo, hi = lo_hi
    return gen.integers(lo, hi + 1, size)


def _distinct(gen, n: np.ndarray, k: np.ndarray, kmax: int):
    """Per row, ``k`` distinct picks in [0, ``n``): (row, pick).

    Steps of 1 .. (n - 1) // (kmax - 1) from a uniform start never wrap a
    full turn within ``kmax`` picks."""
    rows = len(n)
    step_hi = np.maximum((n - 1) // max(kmax - 1, 1), 1)
    steps = 1 + (gen.random((rows, kmax - 1)) * step_hi[:, None]).astype(np.int64)
    start = (gen.random(rows) * n).astype(np.int64)
    pos = np.concatenate([start[:, None], start[:, None] + np.cumsum(steps, 1)], 1)
    pos %= n[:, None]
    keep = np.arange(kmax)[None, :] < k[:, None]
    return np.nonzero(keep)[0], pos[keep]


def generate(cfg: dict, seed: int) -> Dataset:
    del seed  # the dataset is LUBM(universities, uba_seed); see the module doc
    pr = cfg["profile"]
    U, pool = cfg["universities"], pr["university_pool"]
    gen = np.random.default_rng(pr["uba_seed"])

    # ---- counts ------------------------------------------------------- #
    n_dept = _between(gen, pr["departments"], U)
    dept_univ, dept_local, _ = _spans(n_dept)
    nd = len(dept_univ)
    per_rank = np.stack([_between(gen, pr[r], nd) for r in
                         ("full_professors", "associate_professors",
                          "assistant_professors", "lecturers")], 1)
    n_fac = per_rank.sum(1)
    n_ug = n_fac * _between(gen, pr["undergraduates_per_faculty"], nd)
    n_gs = n_fac * _between(gen, pr["graduates_per_faculty"], nd)
    n_rg = _between(gen, pr["research_groups"], nd)

    fac_dept, fac_local, fac0 = _spans(n_fac)
    bounds = np.cumsum(per_rank, 1)
    fac_rank = (fac_local[:, None] >= bounds[fac_dept, :3]).sum(1)
    rank_start = np.concatenate([np.zeros((nd, 1), np.int64), bounds[:, :3]], 1)
    fac_k = fac_local - rank_start[fac_dept, fac_rank]  # index within rank
    n_prof = bounds[:, 2]  # full + associate + assistant
    nf = len(fac_dept)

    n_crs_f = _between(gen, pr["courses_per_faculty"], nf)
    n_gcrs_f = _between(gen, pr["graduate_courses_per_faculty"], nf)
    crs_fac, _, _ = _spans(n_crs_f)
    gcrs_fac, _, _ = _spans(n_gcrs_f)
    n_crs = np.bincount(fac_dept[crs_fac], minlength=nd)
    n_gcrs = np.bincount(fac_dept[gcrs_fac], minlength=nd)
    _, crs_local, crs0 = _spans(n_crs)
    _, gcrs_local, gcrs0 = _spans(n_gcrs)

    pub_lo_hi = np.array([pr["publications_" + r] for r in
                          ("full_professor", "associate_professor",
                           "assistant_professor", "lecturer")])
    lo, hi = pub_lo_hi[fac_rank, 0], pub_lo_hi[fac_rank, 1]
    n_pub_f = lo + (gen.random(nf) * (hi - lo + 1)).astype(np.int64)
    pub_fac, pub_k, pub0_f = _spans(n_pub_f)
    npub = len(pub_fac)

    ug_dept, ug_local, _ = _spans(n_ug)
    gs_dept, gs_local, _ = _spans(n_gs)
    rg_dept, rg_local, _ = _spans(n_rg)
    nu, ng = len(ug_dept), len(gs_dept)

    # ---- node ids, kind by kind --------------------------------------- #
    sizes = {
        "university": pool, "department": nd, "faculty": nf,
        "undergraduate": nu, "graduate": ng, "course": len(crs_fac),
        "graduate_course": len(gcrs_fac), "research_group": len(rg_dept),
        "publication": npub, "email": nf + nu + ng,
        "class": len(CLASSES), "telephone": 1,
        "interest": pr["research_interests"],
        "name_literal": 0, "document": nd, "ontology": 1,
    }
    # local names repeat across departments: one literal node per name
    name_kinds = {
        "University": pool, "Department": int(n_dept.max()),
        **{r: int(per_rank[:, i].max()) for i, r in enumerate(RANKS)},
        "UndergraduateStudent": int(n_ug.max()),
        "GraduateStudent": int(n_gs.max()),
        "Course": int(n_crs.max()), "GraduateCourse": int(n_gcrs.max()),
        "Publication": int(n_pub_f.max()),
    }
    name_off, acc = {}, 0
    for k, c in name_kinds.items():
        name_off[k] = acc
        acc += c
    sizes["name_literal"] = acc
    off, acc = {}, 0
    for k, c in sizes.items():
        off[k] = acc
        acc += c
    n_nodes = acc

    lab = {n: i for i, n in enumerate(LABELS)}
    cls = {n: off["class"] + i for i, n in enumerate(CLASSES)}
    parts = []

    def emit(label, s, o):
        s = np.asarray(s, np.int64)
        parts.append(np.stack([s, np.full(s.size, lab[label], np.int64),
                               np.broadcast_to(np.asarray(o, np.int64), s.shape)],
                              1))

    univ = off["university"] + np.arange(U)
    dept = off["department"] + np.arange(nd)
    fac = off["faculty"] + np.arange(nf)
    ug = off["undergraduate"] + np.arange(nu)
    gs = off["graduate"] + np.arange(ng)
    crs = off["course"] + np.arange(len(crs_fac))
    gcrs = off["graduate_course"] + np.arange(len(gcrs_fac))
    rg = off["research_group"] + np.arange(len(rg_dept))
    pub = off["publication"] + np.arange(npub)

    # universities and departments
    emit("type", univ, cls["University"])
    emit("name", univ, off["name_literal"] + name_off["University"] + np.arange(U))
    emit("type", dept, cls["Department"])
    emit("name", dept, off["name_literal"] + name_off["Department"] + dept_local)
    emit("subOrganizationOf", dept, univ[dept_univ])
    emit("imports", off["document"] + np.arange(nd), off["ontology"])
    emit("type", rg, cls["ResearchGroup"])
    emit("subOrganizationOf", rg, dept[rg_dept])

    # faculty
    emit("type", fac, off["class"] + 2 + fac_rank)
    name_of_rank = np.array([name_off[r] for r in RANKS])
    emit("name", fac, off["name_literal"] + name_of_rank[fac_rank] + fac_k)
    head = fac0 + (gen.random(nd) * per_rank[:, 0]).astype(np.int64)
    is_head = np.zeros(nf, bool)
    is_head[head] = True
    emit("headOf", fac[is_head], dept[fac_dept[is_head]])
    emit("worksFor", fac[~is_head], dept[fac_dept[~is_head]])
    emit("researchInterest", fac,
         off["interest"] + gen.integers(0, pr["research_interests"], nf))
    for deg in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                "doctoralDegreeFrom"):
        emit(deg, fac, off["university"] + gen.integers(0, pool, nf))

    # courses
    emit("type", crs, cls["Course"])
    emit("name", crs, off["name_literal"] + name_off["Course"] + crs_local)
    emit("teacherOf", fac[crs_fac], crs)
    emit("type", gcrs, cls["GraduateCourse"])
    emit("name", gcrs, off["name_literal"] + name_off["GraduateCourse"] + gcrs_local)
    emit("teacherOf", fac[gcrs_fac], gcrs)

    # publications of the faculty
    emit("type", pub, cls["Publication"])
    emit("name", pub, off["name_literal"] + name_off["Publication"] + pub_k)
    emit("publicationAuthor", pub, fac[pub_fac])

    # students
    emit("type", ug, cls["UndergraduateStudent"])
    emit("name", ug, off["name_literal"] + name_off["UndergraduateStudent"] + ug_local)
    emit("memberOf", ug, dept[ug_dept])
    row, pick = _distinct(gen, n_crs[ug_dept],
                          _between(gen, pr["courses_per_undergraduate"], nu),
                          pr["courses_per_undergraduate"][1])
    emit("takesCourse", ug[row], crs[crs0[ug_dept[row]] + pick])
    adv = gen.random(nu) < 1.0 / pr["undergraduate_advisor_one_in"]
    d = ug_dept[adv]
    emit("advisor", ug[adv],
         fac[fac0[d] + (gen.random(d.size) * n_prof[d]).astype(np.int64)])

    emit("type", gs, cls["GraduateStudent"])
    emit("name", gs, off["name_literal"] + name_off["GraduateStudent"] + gs_local)
    emit("memberOf", gs, dept[gs_dept])
    emit("undergraduateDegreeFrom", gs, off["university"] + gen.integers(0, pool, ng))
    row, pick = _distinct(gen, n_gcrs[gs_dept],
                          _between(gen, pr["courses_per_graduate"], ng),
                          pr["courses_per_graduate"][1])
    emit("takesCourse", gs[row], gcrs[gcrs0[gs_dept[row]] + pick])
    emit("advisor", gs, fac[fac0[gs_dept]
                            + (gen.random(ng) * n_prof[gs_dept]).astype(np.int64)])
    # the first graduates of a department assist a course, the next a group
    n_ta = n_gs // _between(gen, pr["graduates_per_teaching_assistant"], nd)
    n_ra = n_gs // _between(gen, pr["graduates_per_research_assistant"], nd)
    ta = gs_local < n_ta[gs_dept]
    d = gs_dept[ta]
    emit("type", gs[ta], cls["TeachingAssistant"])
    emit("teachingAssistantOf", gs[ta],
         crs[crs0[d] + (gen.random(d.size) * n_crs[d]).astype(np.int64)])
    ra = (gs_local >= n_ta[gs_dept]) & (gs_local < (n_ta + n_ra)[gs_dept])
    emit("type", gs[ra], cls["ResearchAssistant"])
    # co-authored publications: distinct ones of the department's faculty
    pub_d0 = pub0_f[fac0]  # first publication of each department
    n_pub_d = np.bincount(fac_dept[pub_fac], minlength=nd)
    k = np.minimum(_between(gen, pr["publications_graduate"], ng),
                   n_pub_d[gs_dept])
    row, pick = _distinct(gen, np.maximum(n_pub_d[gs_dept], 1), k,
                          pr["publications_graduate"][1])
    emit("publicationAuthor", pub[pub_d0[gs_dept[row]] + pick], gs[row])

    # literals of every person
    persons = np.concatenate([fac, ug, gs])
    emit("emailAddress", persons, off["email"] + np.arange(persons.size))
    emit("telephone", persons, off["telephone"])

    triples = np.concatenate(parts).astype(np.int32)

    # ---- names -------------------------------------------------------- #
    def ids(prefix, *cols):
        return [prefix + "_".join(map(str, r))
                for r in np.stack(cols, 1).tolist()]

    du, dl = dept_univ, dept_local
    f_tag = np.array(["FullProf", "AssocProf", "AsstProf", "Lecturer"])
    people = (
        [f"{t}{u}_{d}_{k}" for t, u, d, k in zip(
            f_tag[fac_rank].tolist(), du[fac_dept].tolist(),
            dl[fac_dept].tolist(), fac_k.tolist())]
        + ids("UGStudent", du[ug_dept], dl[ug_dept], ug_local)
        + ids("GradStudent", du[gs_dept], dl[gs_dept], gs_local)
    )
    names = (
        [f"Univ{u}" for u in range(pool)]
        + ids("Dept", du, dl)
        + people
        + ids("Course", du[fac_dept[crs_fac]], dl[fac_dept[crs_fac]], crs_local)
        + ids("GradCourse", du[fac_dept[gcrs_fac]], dl[fac_dept[gcrs_fac]],
              gcrs_local)
        + ids("ResearchGroup", du[rg_dept], dl[rg_dept], rg_local)
        + [f"{people[f]}_Pub{k}" for f, k in zip(pub_fac.tolist(), pub_k.tolist())]
        + [f"mail:{p}" for p in people]
        + [f"class:{c}" for c in CLASSES]
        + ["tel:xxx-xxx-xxxx"]
        + [f"Research{i}" for i in range(pr["research_interests"])]
        + [f"name:{k}{i}" for k, c in name_kinds.items() for i in range(c)]
        + ids("doc:Univ", du, dl)
        + ["univ-bench.owl"]
    )
    assert len(names) == n_nodes
    prof = fac[fac_rank < 3]
    kinds = {
        "university": univ, "department": dept, "professor": prof,
        "faculty": fac, "undergraduate": ug, "graduate": gs,
        "course": crs, "publication": pub, "research_group": rg,
    }
    return Dataset(triples, names, list(LABELS), kinds)
