"""The benchmark's TPC-DS q67 at a small size on the CPU: the program through
``dataframe()`` and through ``sql()`` against the plain numpy reference under
the configuration's own limits, the float32 control, the faults the comparison
has to catch, and the two generators side by side under one root.

Data comes from ``benchmark/tpcds_datagen.py`` at SF 0.01, seeded; nothing
here reads the program's own TPC-DS rig.
"""
from __future__ import annotations

import json
import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for p in (os.path.join(BENCH, "queries"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import datagen  # noqa: E402
import ds_q67  # noqa: E402
import run as harness  # noqa: E402
import tpcds_datagen  # noqa: E402

SF, SEED = 0.01, 2147485127  # a seed past 32 signed bits, as the driver's are
PARAMS = ds_q67.DEFAULT_PARAMS


@pytest.fixture(scope="module")
def config():
    return harness.load_config("tpcds_sf1_parquet")


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ds") / f"sf{SF:g}-seed{SEED}")
    out = tpcds_datagen.ensure_tables(root, SF, SEED, list(ds_q67.TABLES), 8)
    assert out.pop("_generated")
    return out


@pytest.fixture(scope="module")
def read(paths):
    return harness.table_reader(paths)


@pytest.fixture(scope="module")
def want(read):
    return ds_q67.reference(read, PARAMS)


@pytest.fixture(scope="module")
def strict_session(config):
    from spark_rapids_tpu import TpuSession

    return TpuSession(dict(config["conf"]))


def _verdict(rows, want, config, names=ds_q67.RESULT_COLUMNS):
    numbers = compare.compare(
        [("q", list(names), rows)], {"q": (list(ds_q67.RESULT_COLUMNS), want)}
    )
    ok, _ = compare.verdict(numbers, config["limits"])
    return ok, numbers


def _program_df(entry, s, paths):
    if entry == "dataframe":
        return ds_q67.dataframe(lambda name: s.read.parquet(paths[name]), PARAMS)
    for name in ds_q67.TABLES:
        s.read.parquet(paths[name]).create_or_replace_temp_view(name)
    return s.sql(ds_q67.sql(PARAMS))


def _program_rows(entry, s, paths):
    df = _program_df(entry, s, paths)
    return list(df.columns), df.collect()


@pytest.mark.parametrize("entry", ["dataframe", "sql"])
def test_program_matches_reference(entry, strict_session, paths, want, config):
    names, rows = _program_rows(entry, strict_session, paths)
    ok, numbers = _verdict(rows, want, config, names)
    assert ok, numbers
    assert len(rows) == 100 and numbers["rows_wrong"] == 0
    # ranks 82..100 of a category are product rows, each tied with the row
    # that adds d_year (DMS selects one calendar year): ties by construction
    ranks = [r[-1] for r in want]
    assert len(ranks) - len(set(ranks)) >= 5


@pytest.mark.parametrize("entry", ["dataframe", "sql"])
def test_pruned_through_the_rollup(entry, strict_session, paths):
    """The rollup's Expand carries only what the aggregate reads, by either
    entry: unpruned, all 102 columns of the four tables ran through three
    joins and nine copies. The plan that runs is ``_prepare_plan``'s;
    ``DataFrame.explain()`` plans without ``prune_columns`` and prints all
    102 for a pruned query too."""
    from spark_rapids_tpu.exec.tpu import TpuExpandExec

    plan, _ = strict_session._prepare_plan(_program_df(entry, strict_session, paths)._plan)

    def find(p):
        if isinstance(p, TpuExpandExec):
            return p
        return next((f for f in map(find, p.children) if f is not None), None)

    expand = find(plan)
    assert len(expand.projections) == 9
    assert len(expand.output.names) == 2 + 8 + 1  # two inputs, eight keys, the grouping id
    assert len(expand.children[0].output.names) <= 17


def _flipped_rank(rows):
    rows = [list(r) for r in rows]
    rows[3][-1], rows[4][-1] = rows[4][-1] + 1, rows[3][-1] + 1
    return rows


def _dropped_level(rows):
    """The answer without the rows of one rollup level (brand rows: i_brand
    set, i_product_name rolled up)."""
    return [r for r in rows if not (r[2] is not None and r[3] is None)]


def _tie_broken_by_an_ulp(rows):
    """One of two tied sums nudged up by one ulp, as a sum taken in another
    order would be, and the ranks recomputed as the program would."""
    rows = [list(r) for r in rows]
    sums = [r[8] for r in rows]
    i = next(k for k, v in enumerate(sums) if sums.count(v) > 1)
    tied_rank = rows[i][-1]
    rows[i][8] = math.nextafter(rows[i][8], math.inf)
    for r in rows:  # the other row of the tie now ranks one lower
        if r[-1] == tied_rank and r is not rows[i]:
            r[-1] += 1
    return rows


@pytest.mark.parametrize(
    "fault", [_flipped_rank, _dropped_level, _tie_broken_by_an_ulp],
    ids=["flipped_rank", "dropped_rollup_level", "tie_broken_by_one_ulp"],
)
def test_fault_reads_rows_wrong(fault, want, config):
    got = fault(want)
    assert got != [list(r) for r in want]
    ok, numbers = _verdict(got, want, config)
    assert not ok and numbers["rows_wrong"] == 1, numbers


def test_clean_answer_reads_correct(want, config):
    ok, numbers = _verdict([tuple(r) for r in want], want, config)
    assert ok and numbers == {"answers_compared": 1, "rows_wrong": 0, "float_rel_gap": 0.0}


def test_float32_control_is_not_correct(read, want, config):
    got = ds_q67.reference(read, PARAMS, dtype=np.float32)
    ok, numbers = _verdict(got, want, config)
    assert not ok, numbers
    assert numbers["rows_wrong"] or numbers["float_rel_gap"] > config["limits"]["float_rel_gap"]


def test_levels_add_up(read):
    levels, domains = ds_q67.rollup_levels(read, PARAMS)
    assert len(levels) == 9 and len(domains) == 8
    grand = float(levels[0][1][0])
    assert len(levels[0][1]) == 1 and grand > 0
    for depth, (group, sums) in enumerate(levels):
        assert math.isclose(float(np.sum(sums)), grand, rel_tol=1e-12), depth
        assert (group[:, :depth] >= 0).all() and (group[:, depth:] == -1).all()
        assert len(np.unique(group, axis=0)) == len(group)
    # one calendar year: the level that adds d_year sums the same rows
    assert np.array_equal(levels[4][1], levels[5][1])
    assert [len(g) for g, _ in levels] == sorted(len(g) for g, _ in levels)


def test_month_seq_counts_from_1900(read):
    dd = read("date_dim", ["d_month_seq", "d_year", "d_moy"])
    picked = (dd["d_month_seq"] >= 1200) & (dd["d_month_seq"] <= 1211)
    assert set(dd["d_year"][picked]) == {2000} and picked.sum() == 366
    assert np.array_equal(dd["d_month_seq"], (dd["d_year"] - 1900) * 12 + dd["d_moy"] - 1)


def test_shapes_the_specification_fixes(read):
    """Business ids are char(16) and date_dim is the 73,049 days from
    1900-01-02 with the julian day number as its key: ``s_store_id`` is a
    rollup key, and its padded width sets the sort's key words."""
    ids = read("store", ["s_store_id"])["s_store_id"]
    assert {len(x) for x in ids} == {16} and len(set(ids)) == len(ids)
    assert {len(x) for x in read("item", ["i_item_id"])["i_item_id"]} == {16}
    dd = read("date_dim", ["d_date_sk", "d_date", "d_year"])
    assert len(dd["d_date_sk"]) == 73049 and dd["d_date_sk"][0] == 2415022
    assert np.array_equal(dd["d_date_sk"] - 2440588, dd["d_date"])  # days since 1970
    assert (dd["d_year"][0], dd["d_year"][-1]) == (1900, 2100)


def _listing(root):
    return sorted(
        (os.path.relpath(os.path.join(d, f), root), os.path.getmtime(os.path.join(d, f)))
        for d, _, files in os.walk(root) for f in files
    )


@pytest.mark.parametrize("first", ["tpch", "tpcds"])
def test_generators_leave_each_other_alone(first, tmp_path):
    """Both configurations are SF 1 under one ``sf<sf>-seed<seed>`` root: each
    generator keeps a marker of its own and removes only its own tables."""
    root = str(tmp_path / "sf0.01-seed7")
    gens = {
        "tpch": (datagen, ["lineitem"], datagen.MARKER),
        "tpcds": (tpcds_datagen, ["item", "store"], tpcds_datagen.MARKER),
    }
    order = [first] + [k for k in gens if k != first]
    assert datagen.MARKER != tpcds_datagen.MARKER
    for name in order:
        gen, tables, _ = gens[name]
        assert gen.ensure_tables(root, 0.01, 7, tables, 2)["_generated"]
    before = _listing(root)
    for name in order + order:  # found again, nothing rewritten, by either
        gen, tables, marker = gens[name]
        assert not gen.ensure_tables(root, 0.01, 7, tables, 2)["_generated"]
        with open(os.path.join(root, marker)) as f:
            assert set(tables) <= set(json.load(f)["tables"])
    assert _listing(root) == before
    # a further table of one generator leaves the other's files as they were
    theirs = [e for e in before if e[0].split(os.sep)[0] in ("lineitem", "orders", datagen.MARKER)]
    assert tpcds_datagen.ensure_tables(root, 0.01, 7, ["date_dim"], 2)["_generated"]
    assert [e for e in _listing(root) if e in theirs] == theirs


def test_generator_is_seeded(tmp_path):
    import pyarrow.parquet as pq

    a = tpcds_datagen.ensure_tables(str(tmp_path / "a"), 0.01, SEED, ["store_sales"], 2)
    b = tpcds_datagen.ensure_tables(str(tmp_path / "b"), 0.01, SEED, ["store_sales"], 2)
    c = tpcds_datagen.ensure_tables(str(tmp_path / "c"), 0.01, SEED + 1, ["store_sales"], 2)
    ta, tb, tc = (pq.read_table(x["store_sales"]) for x in (a, b, c))
    assert ta.equals(tb) and not ta.equals(tc)
    assert ta.num_columns == 23 and ta.num_rows == 28800
    nulls = ta.column("ss_sold_date_sk").null_count / ta.num_rows
    assert 0.01 < nulls < 0.03
    # a write from before the tables' contents last changed is made anew
    with open(os.path.join(str(tmp_path / "a"), tpcds_datagen.MARKER), "w") as f:
        json.dump({"sf": 0.01, "seed": SEED, "tables": ["store_sales"]}, f)
    assert tpcds_datagen.ensure_tables(str(tmp_path / "a"), 0.01, SEED, ["store_sales"], 2)["_generated"]
