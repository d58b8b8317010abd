"""Engine DataFrame tests — partitioned execution, retry, columnar UDFs."""

import os
import threading

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from sparkdl_tpu.engine import DataFrame, EngineConfig, TaskFailure
from sparkdl_tpu.engine.dataframe import column_to_numpy, fixed_size_list_array


def make_df(n=10, parts=3):
    return DataFrame.fromPandas(
        pd.DataFrame({"x": np.arange(n, dtype=np.int64),
                      "y": np.arange(n, dtype=np.float64) * 2.0}),
        numPartitions=parts)


def test_partitioning_and_count():
    df = make_df(10, 3)
    assert df.numPartitions == 3
    assert df.count() == 10
    assert df.columns == ["x", "y"]


def test_collect_order_preserved():
    df = make_df(10, 4)
    rows = df.collect()
    assert [r["x"] for r in rows] == list(range(10))


def test_select_drop_rename():
    df = make_df()
    assert df.select("y").columns == ["y"]
    assert df.drop("x").columns == ["y"]
    assert df.withColumnRenamed("x", "z").columns == ["z", "y"]
    with pytest.raises(KeyError):
        df.select("nope")


def test_with_column_rowwise():
    df = make_df(6, 2)
    out = df.withColumn("sum", lambda x, y: float(x) + y,
                        inputCols=["x", "y"], outputType=pa.float64())
    rows = out.collect()
    assert all(r["sum"] == r["x"] + r["y"] for r in rows)


def test_with_column_batch_vectorized():
    df = make_df(8, 3)

    def double(batch: pa.RecordBatch) -> pa.Array:
        x = column_to_numpy(batch.column(0))
        return pa.array(x * 2)

    rows = df.withColumnBatch("x2", double, outputType=pa.int64()).collect()
    assert all(r["x2"] == 2 * r["x"] for r in rows)


def test_filter_and_dropna():
    df = make_df(10, 2)
    assert df.filter(lambda x: x % 2 == 0, inputCols=["x"]).count() == 5
    df2 = DataFrame.fromRows([{"a": 1}, {"a": None}, {"a": 3}])
    assert df2.dropna().count() == 2


def test_limit_union_repartition():
    df = make_df(10, 3)
    assert df.limit(4).count() == 4
    assert df.union(make_df(5, 1)).count() == 15
    assert df.repartition(5).numPartitions == 5
    assert df.repartition(5).count() == 10


def test_lazy_ops_compose():
    df = make_df(10, 2)
    out = (df.withColumn("a", lambda x: x + 1, ["x"], pa.int64())
             .withColumn("b", lambda a: a * 10, ["a"], pa.int64())
             .select("b"))
    assert [r["b"] for r in out.collect()] == [(i + 1) * 10 for i in range(10)]


def test_retry_recovers_transient_failure():
    df = make_df(6, 3)
    failures = {"left": 1}

    def injector(pidx, attempt):
        if pidx == 1 and failures["left"] > 0:
            failures["left"] -= 1
            raise RuntimeError("transient")

    EngineConfig.fault_injector = injector
    try:
        assert df.withColumn("z", lambda x: x, ["x"], pa.int64()).count() == 6
    finally:
        EngineConfig.fault_injector = None


def test_retry_exhaustion_raises():
    df = make_df(6, 3)

    def injector(pidx, attempt):
        if pidx == 0:
            raise RuntimeError("permanent")

    EngineConfig.fault_injector = injector
    try:
        with pytest.raises(TaskFailure):
            df.withColumn("z", lambda x: x, ["x"], pa.int64()).count()
    finally:
        EngineConfig.fault_injector = None


def test_fixed_size_list_roundtrip(rng):
    mat = rng.standard_normal((5, 7)).astype(np.float32)
    arr = fixed_size_list_array(mat)
    assert arr.type == pa.list_(pa.float32(), 7)
    back = column_to_numpy(arr)
    np.testing.assert_array_equal(mat, back)


def test_from_columns_ndarray(rng):
    feats = rng.standard_normal((4, 3)).astype(np.float32)
    df = DataFrame.fromColumns({"id": list(range(4)), "f": feats})
    back = column_to_numpy(df.toArrow().column("f"))
    np.testing.assert_array_equal(back, feats)


def test_cache_materializes_once():
    calls = {"n": 0}
    df = make_df(4, 2)

    def op(batch):
        calls["n"] += 1
        return pa.array([1] * batch.num_rows)

    out = df.withColumnBatch("one", op, pa.int64()).cache()
    out.collect()
    out.collect()
    assert calls["n"] == 2  # once per partition, not per collect


def test_with_column_no_output_type_then_select():
    # Regression: declared null-typed schema must not be forced onto batches.
    df = make_df(6, 2)
    out = df.withColumn("name", lambda x: f"row{x}", ["x"]).select("name")
    assert [r["name"] for r in out.collect()] == [f"row{i}" for i in range(6)]


def test_heterogeneous_inferred_types_unify():
    # Partition 0 infers null type, partition 1 infers int64 -> unify.
    df = DataFrame.fromRows([{"x": 1}, {"x": 2}], numPartitions=2)
    out = df.withColumn("y", lambda x: None if x == 1 else x, ["x"])
    rows = out.collect()
    assert rows[0]["y"] is None and rows[1]["y"] == 2


def test_cache_reused_by_derived_frames():
    calls = {"n": 0}
    df = make_df(4, 2)

    def op(batch):
        calls["n"] += 1
        return pa.array([1.0] * batch.num_rows)

    cached = df.withColumnBatch("c", op, pa.float64()).cache()
    n_after_cache = calls["n"]
    cached.select("c").collect()
    assert calls["n"] == n_after_cache  # derived frame reused materialization


def test_with_column_replace_keeps_position():
    df = DataFrame.fromRows([{"a": 1, "b": 2}], numPartitions=1)
    out = df.withColumn("a", lambda a: a * 10, ["a"], pa.int64())
    assert out.columns == ["a", "b"]
    assert out.collect() == [{"a": 10, "b": 2}]


def test_limit_materializes_only_needed_partitions():
    calls = {"n": 0}

    def op(batch):
        calls["n"] += 1
        return pa.array([1] * batch.num_rows)

    big = DataFrame.fromRows([{"x": i} for i in range(100)], numPartitions=10)
    assert big.withColumnBatch("y", op, pa.int64()).limit(5).count() == 5
    assert calls["n"] == 1


def test_select_expr_star_literals_aliases(rng):
    df = DataFrame.fromColumns({"a": np.arange(4, dtype=np.int64),
                                "b": np.arange(4, dtype=np.float32)})
    out = df.selectExpr("*", "7 as seven", "'x' as tag", "a as a2")
    rows = out.collect()
    assert out.columns == ["a", "b", "seven", "tag", "a2"]
    assert rows[0]["seven"] == 7 and rows[0]["tag"] == "x"
    assert [r["a2"] for r in rows] == [0, 1, 2, 3]


def test_select_expr_nested_and_multi_arg_udfs(rng):
    from sparkdl_tpu.udf import registerUDF, udf_registry

    registerUDF("sq_test", lambda v: v * v)
    registerUDF("addc_test", lambda a, b: a + b, arity=2)
    try:
        df = DataFrame.fromColumns({"x": np.arange(4, dtype=np.int64),
                                    "y": np.arange(4, dtype=np.int64)})
        out = df.selectExpr("addc_test(sq_test(x), y) as z").collect()
        assert [r["z"] for r in out] == [0, 2, 6, 12]
        # default name is the trimmed expression text
        out2 = df.selectExpr("sq_test( x )")
        assert out2.columns == ["sq_test( x )"]
    finally:
        udf_registry.unregister("sq_test")
        udf_registry.unregister("addc_test")


def test_select_expr_arity_and_parse_errors(rng):
    from sparkdl_tpu.udf import registerUDF, udf_registry

    registerUDF("one_arg_test", lambda v: v)
    try:
        df = DataFrame.fromColumns({"x": np.arange(3, dtype=np.int64)})
        with pytest.raises(ValueError, match="argument"):
            df.selectExpr("one_arg_test(x, x)")
        with pytest.raises(ValueError, match="Cannot tokenize|Unexpected|Trailing"):
            df.selectExpr("x + 1")
        with pytest.raises(KeyError, match="nope"):
            df.selectExpr("nope")
    finally:
        udf_registry.unregister("one_arg_test")


def test_stream_partitions_order(rng):
    df = DataFrame.fromColumns({"v": np.arange(12, dtype=np.int64)},
                               numPartitions=4)
    df = df.withColumn("w", lambda v: v + 1, inputCols=["v"])
    natural = [p.column(0).to_pylist() for p in df.streamPartitions()]
    order = [2, 0, 3, 1]
    permuted = [p.column(0).to_pylist()
                for p in df.streamPartitions(order=order)]
    assert permuted == [natural[i] for i in order]
    # cached frames honor order too
    df.cache().collect() if hasattr(df, "cache") else None
    df2 = df
    df2.toArrow()  # materializes
    permuted2 = [p.column(0).to_pylist()
                 for p in df2.streamPartitions(order=order)]
    assert permuted2 == permuted


def test_order_by():
    df = DataFrame.fromRows(
        [{"a": 3, "b": "x"}, {"a": 1, "b": "y"}, {"a": 2, "b": "z"}],
        numPartitions=2)
    assert [r["a"] for r in df.orderBy("a").collect()] == [1, 2, 3]
    assert [r["a"] for r in df.orderBy("a", ascending=False).collect()] == \
        [3, 2, 1]
    with pytest.raises(KeyError):
        df.orderBy("nope")


def test_order_by_multi_key():
    rows = [{"g": "b", "v": 1}, {"g": "a", "v": 2}, {"g": "a", "v": 1}]
    df = DataFrame.fromRows(rows)
    got = df.orderBy("g", "v", ascending=[True, False]).collect()
    assert [(r["g"], r["v"]) for r in got] == [("a", 2), ("a", 1), ("b", 1)]


def test_group_by_count_and_agg():
    rows = [{"g": "a", "v": 1.0}, {"g": "a", "v": 3.0}, {"g": "b", "v": 5.0}]
    df = DataFrame.fromRows(rows, numPartitions=2)
    counts = {r["g"]: r["count"] for r in df.groupBy("g").count().collect()}
    assert counts == {"a": 2, "b": 1}
    sums = {r["g"]: r["sum(v)"]
            for r in df.groupBy("g").agg({"v": "sum"}).collect()}
    assert sums == {"a": 4.0, "b": 5.0}
    out = df.groupBy("g").agg({"v": "mean"}).orderBy("g").collect()
    assert out[0]["mean(v)"] == 2.0 and out[1]["mean(v)"] == 5.0
    with pytest.raises(ValueError, match="Unsupported aggregate"):
        df.groupBy("g").agg({"v": "median"})


def test_group_by_convenience_mean_sum():
    rows = [{"g": 1, "v": 2.0}, {"g": 1, "v": 4.0}, {"g": 2, "v": 10.0}]
    df = DataFrame.fromRows(rows)
    m = {r["g"]: r["mean(v)"] for r in df.groupBy("g").mean("v").collect()}
    assert m == {1: 3.0, 2: 10.0}
    s = {r["g"]: r["sum(v)"] for r in df.groupBy("g").sum("v").collect()}
    assert s == {1: 6.0, 2: 10.0}


# -- multi-host transform primitives (VERDICT r4 #1) ------------------------

def test_process_shard_partitions_and_idempotence():
    import pyarrow as pa

    from sparkdl_tpu.engine.dataframe import DataFrame

    df = DataFrame.fromRows([{"i": i} for i in range(12)], numPartitions=4)
    shards = [df.processShard(process_id=p, num_processes=3)
              for p in range(3)]
    seen = [set(r["i"] for r in s.collect()) for s in shards]
    assert set().union(*seen) == set(range(12))
    assert sum(len(s) for s in seen) == 12  # disjoint + exhaustive
    # lazy ops on a shard keep provenance and don't re-shard
    derived = shards[0].select("i")
    assert derived._process_shard == (0, 3)
    assert derived.processShard(process_id=1, num_processes=3) is derived
    # single process is a no-op
    assert df.processShard(process_id=0, num_processes=1) is df
    with pytest.raises(ValueError, match="process_id"):
        df.processShard(process_id=3, num_processes=3)


def test_reinterleave_shards_restores_order():
    import pyarrow as pa

    from sparkdl_tpu.engine.dataframe import (DataFrame,
                                              _deserialize_batches,
                                              _reinterleave_shards,
                                              _serialize_batches)

    df = DataFrame.fromRows([{"i": i} for i in range(10)], numPartitions=5)
    n = 2
    per_host = []
    for p in range(n):
        shard = df.processShard(process_id=p, num_processes=n)
        payload = _serialize_batches(shard._materialize(), shard.schema)
        per_host.append(_deserialize_batches(payload))
    parts, schema = _reinterleave_shards(per_host, df.schema)
    rebuilt = DataFrame(parts, schema)
    assert [r["i"] for r in rebuilt.collect()] == list(range(10))


# -- SQL serving surface: where(), temp views, sql() (VERDICT r4 #10) -------

def test_where_comparisons_and_null_semantics():
    from sparkdl_tpu.engine.dataframe import DataFrame

    rows = [{"i": 0, "s": "a", "x": 1.0}, {"i": 1, "s": "b", "x": None},
            {"i": 2, "s": "a", "x": 3.0}, {"i": 3, "s": None, "x": 4.0}]
    df = DataFrame.fromRows(rows, numPartitions=2)
    assert [r["i"] for r in df.where("i >= 2").collect()] == [2, 3]
    assert [r["i"] for r in df.where("s = 'a'").collect()] == [0, 2]
    assert [r["i"] for r in df.where("s != 'a'").collect()] == [1]
    # NULL comparisons are not-true (SQL semantics): row 1 (x NULL) and
    # row 3 (s NULL) drop from comparisons on those columns
    assert [r["i"] for r in df.where("x < 10").collect()] == [0, 2, 3]
    assert [r["i"] for r in df.where("x IS NULL").collect()] == [1]
    assert [r["i"] for r in df.where("s is not null AND x > 1").collect()] \
        == [2]
    assert [r["i"] for r in df.where("i = 0 OR (i > 1 AND s = 'a')")
            .collect()] == [0, 2]
    assert [r["i"] for r in df.where("NOT i < 2").collect()] == [2, 3]
    with pytest.raises(KeyError, match="nope"):
        df.where("nope = 1")
    with pytest.raises(ValueError, match="WHERE"):
        df.where("f(i) = 1")


def test_sql_over_temp_view():
    from sparkdl_tpu.engine.dataframe import DataFrame, sql, table

    rows = [{"i": i, "lab": i % 2} for i in range(6)]
    df = DataFrame.fromRows(rows, numPartitions=2)
    df.createOrReplaceTempView("rows_view")
    assert table("rows_view") is df
    out = sql("SELECT i, lab AS y FROM rows_view WHERE lab = 1").collect()
    assert [r["i"] for r in out] == [1, 3, 5]
    assert all(set(r) == {"i", "y"} for r in out)
    # star + literal projection, keyword case-insensitivity
    out = sql("select *, 7 as seven from rows_view where i >= 4").collect()
    assert [(r["i"], r["seven"]) for r in out] == [(4, 7), (5, 7)]
    with pytest.raises(KeyError, match="no_view"):
        sql("SELECT i FROM no_view")
    with pytest.raises(ValueError, match="SELECT"):
        sql("UPDATE rows_view")


def test_sql_with_registered_udf(rng):
    """The reference's exact serving string (SURVEY.md §3.4):
    SELECT udf(image_col) FROM view, via a registered tensor UDF."""
    from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
    from sparkdl_tpu.engine.dataframe import DataFrame, sql
    from sparkdl_tpu.udf import registerTensorUDF

    import jax.numpy as jnp

    mf = ModelFunction(lambda v, x: x * v["scale"] + 1.0,
                       {"scale": jnp.asarray(2.0)},
                       TensorSpec((None, 3), "float32"), name="affine")
    registerTensorUDF("affine_udf", mf, batchSize=4)
    x = rng.normal(size=(5, 3)).astype(np.float32)
    df = DataFrame.fromColumns({"vec": x, "keep": np.arange(5)})
    df.createOrReplaceTempView("tensors")
    out = sql("SELECT affine_udf(vec) AS out, keep FROM tensors "
              "WHERE keep != 2").collect()
    assert [r["keep"] for r in out] == [0, 1, 3, 4]
    want = x * 2.0 + 1.0
    for r in out:
        np.testing.assert_allclose(r["out"], want[r["keep"]], rtol=1e-6)


def test_where_constant_predicate():
    from sparkdl_tpu.engine.dataframe import DataFrame

    df = DataFrame.fromRows([{"i": i} for i in range(4)], numPartitions=2)
    assert len(df.where("1 = 1").collect()) == 4
    assert len(df.where("1 = 2").collect()) == 0


def test_distinct_and_sample():
    from sparkdl_tpu.engine.dataframe import DataFrame

    rows = [{"a": i % 3, "b": "x" if i % 2 else "y"} for i in range(12)]
    df = DataFrame.fromRows(rows, numPartitions=3)
    d = df.distinct().collect()
    assert len(d) == 6  # 3 x 2 combinations
    assert len({(r["a"], r["b"]) for r in d}) == 6
    # first-occurrence order
    assert d[0] == {"a": 0, "b": "y"} and d[1] == {"a": 1, "b": "x"}

    big = DataFrame.fromRows([{"i": i} for i in range(1000)],
                             numPartitions=4)
    s = big.sample(0.3, seed=7)
    n = s.count()
    assert 230 <= n <= 370  # Bernoulli around 300
    # deterministic in seed
    assert [r["i"] for r in big.sample(0.3, seed=7).collect()] == \
        [r["i"] for r in s.collect()]
    with pytest.raises(ValueError, match="fraction"):
        big.sample(1.5)


def test_distinct_nested_columns():
    from sparkdl_tpu.engine.dataframe import DataFrame

    rows = [{"s": {"k": [1, 2]}}, {"s": {"k": [1, 2]}}, {"s": {"k": [3]}}]
    df = DataFrame.fromRows(rows, numPartitions=2)
    assert len(df.distinct().collect()) == 2


def test_join_inner_left_and_guards():
    from sparkdl_tpu.engine.dataframe import DataFrame

    left = DataFrame.fromRows(
        [{"id": 1, "x": "a"}, {"id": 2, "x": "b"}, {"id": 2, "x": "c"},
         {"id": 3, "x": "d"}, {"id": None, "x": "e"}], numPartitions=2)
    right = DataFrame.fromRows(
        [{"id": 1, "y": 10}, {"id": 2, "y": 20}, {"id": 2, "y": 21},
         {"id": 9, "y": 90}, {"id": None, "y": 99}], numPartitions=2)

    inner = left.join(right, on="id").collect()
    # id=1 -> 1 pair; id=2 -> 2 left x 2 right = 4 pairs; nulls never match
    assert len(inner) == 5
    assert {(r["id"], r["x"], r["y"]) for r in inner} == {
        (1, "a", 10), (2, "b", 20), (2, "b", 21), (2, "c", 20),
        (2, "c", 21)}
    assert set(inner[0]) == {"id", "x", "y"}  # key appears once

    lj = left.join(right, on="id", how="left").collect()
    assert len(lj) == 7  # 5 matches + id=3 + null-key row
    unmatched = [r for r in lj if r["y"] is None]
    assert {r["x"] for r in unmatched} == {"d", "e"}

    with pytest.raises(ValueError, match="duplicate columns"):
        left.join(DataFrame.fromRows([{"id": 1, "x": "z"}]), on="id")
    with pytest.raises(KeyError, match="right"):
        left.join(DataFrame.fromRows([{"k": 1}]), on="id")
    with pytest.raises(ValueError, match="how"):
        left.join(right, on="id", how="outer")
    # empty result keeps the joined schema
    empty = DataFrame.fromRows([{"id": 77, "x": "q"}]).join(right, on="id")
    assert empty.count() == 0
    assert empty.columns == ["id", "x", "y"]


def test_join_multi_key():
    from sparkdl_tpu.engine.dataframe import DataFrame

    left = DataFrame.fromRows([{"a": 1, "b": "u", "x": 1.0},
                               {"a": 1, "b": "v", "x": 2.0}])
    right = DataFrame.fromRows([{"a": 1, "b": "u", "y": 5.0}])
    out = left.join(right, on=["a", "b"]).collect()
    assert out == [{"a": 1, "b": "u", "x": 1.0, "y": 5.0}]


def test_join_preserves_types_and_order():
    import pyarrow as pa

    from sparkdl_tpu.engine.dataframe import DataFrame

    # key column NOT leftmost; unmatched left join must keep right's
    # int64 dtype (all-null column would otherwise infer as null type)
    left = DataFrame.fromRows([{"x": "a", "id": 7}], numPartitions=1)
    right = DataFrame.fromRows([{"id": 1, "y": 10}], numPartitions=1)
    out = left.join(right, on="id", how="left")
    assert out.columns == ["x", "id", "y"]
    table = out.toArrow()
    assert table.schema.field("y").type == pa.int64()
    assert out.collect() == [{"x": "a", "id": 7, "y": None}]
    # matched and unmatched results share one column order
    both = DataFrame.fromRows([{"x": "a", "id": 1}]).join(right, on="id")
    assert both.columns == ["x", "id", "y"]
    # feature-vector columns survive a join with their list type
    feats = DataFrame.fromColumns({"f": np.ones((2, 4), np.float32),
                                   "id": np.asarray([1, 2])})
    joined = feats.join(right, on="id").toArrow()
    assert pa.types.is_fixed_size_list(joined.schema.field("f").type)


def test_join_on_nested_key():
    from sparkdl_tpu.engine.dataframe import DataFrame

    left = DataFrame.fromRows([{"k": [1, 2], "x": "a"},
                               {"k": [3], "x": "b"}])
    right = DataFrame.fromRows([{"k": [1, 2], "y": 1.0}])
    out = left.join(right, on="k").collect()
    assert out == [{"k": [1, 2], "x": "a", "y": 1.0}]


def test_eval_bool_short_circuits():
    """AND/OR stop at the first deciding operand: the right side references
    a column missing from the env, so evaluating it would KeyError."""
    from sparkdl_tpu.engine import sql_expr

    and_node = sql_expr.parse_bool("a = 1 AND missing = 2")
    assert sql_expr.eval_bool(and_node, {"a": 2}) is False  # no KeyError
    or_node = sql_expr.parse_bool("a = 1 OR missing = 2")
    assert sql_expr.eval_bool(or_node, {"a": 1}) is True
    # an undecided AND/OR must still evaluate everything
    with pytest.raises(KeyError):
        sql_expr.eval_bool(and_node, {"a": 1})
    # SQL UNKNOWN semantics preserved after the rewrite
    null_and = sql_expr.parse_bool("a = 1 AND b = 2")
    assert sql_expr.eval_bool(null_and, {"a": None, "b": 2}) is None
    assert sql_expr.eval_bool(null_and, {"a": None, "b": 3}) is False
    null_or = sql_expr.parse_bool("a = 1 OR b = 2")
    assert sql_expr.eval_bool(null_or, {"a": None, "b": 2}) is True
    assert sql_expr.eval_bool(null_or, {"a": None, "b": 3}) is None


# ---------------------------------------------------------------------------
# sparkdl.row_assembly: the Arrow table → Python rows / pandas step of
# collect() and toPandas(), named so a traced run can see it (PR 30)
# ---------------------------------------------------------------------------


def _mapped_df():
    return make_df(12, 3).withColumn(
        "sum", lambda x, y: float(x) + y, inputCols=["x", "y"],
        outputType=pa.float64())


@pytest.mark.parametrize("method,to", [("collect", "pylist"),
                                       ("toPandas", "pandas")])
def test_row_assembly_span_follows_materialize(method, to):
    from sparkdl_tpu.core import telemetry

    with telemetry.Telemetry() as tel:
        getattr(_mapped_df(), method)()
    spans = tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY)
    (materialize,) = tel.tracer.spans(telemetry.SPAN_MATERIALIZE)
    # collect(): the carried columns' slices built ahead lie inside
    # materialize; every row is in exactly one of the other spans
    ahead = [s for s in spans if s["attributes"].get("ahead")]
    assembly = [s for s in spans if s not in ahead]
    assert all(s["parent_id"] == materialize["span_id"] for s in ahead)
    assert sum(s["attributes"]["rows"] for s in assembly) == 12
    assert all(s["attributes"]["to"] == to and s["attributes"]["bytes"] > 0
               for s in spans)
    # toPandas(): the table is whole before the one assembly starts.
    # collect(): a partition assembled while others still ran lies inside
    # materialize, the rest (the last to resolve among them) after it
    early = [s for s in assembly
             if s["parent_id"] == materialize["span_id"]]
    late = [s for s in assembly if s not in early]
    assert late and all(s["start_ns"] >= materialize["end_ns"]
                        for s in late)
    assert all(s["end_ns"] <= materialize["end_ns"] for s in early)
    if method == "toPandas":
        assert len(assembly) == 1
    else:   # one span a partition, or one over the whole table
        assert sorted(s["attributes"].get("partition", 0)
                      for s in assembly) in ([0], [0, 1, 2])


def test_row_assembly_without_scope_feeds_the_phase_timer_only(monkeypatch):
    from sparkdl_tpu.core import profiling, telemetry

    assert telemetry.active() is None
    made = []
    monkeypatch.setattr(telemetry.Tracer, "span",
                        lambda *a, **k: made.append(a) or telemetry.NULL_SPAN)
    before = profiling.phase_stats().get(telemetry.SPAN_ROW_ASSEMBLY,
                                         {"count": 0})["count"]
    df = _mapped_df()
    # one timing a partition and one a slice of carried cells built ahead
    # (a partition's 4 rows are one slice), or one for the whole table
    df.collect()
    mid = profiling.phase_stats()[telemetry.SPAN_ROW_ASSEMBLY]["count"]
    df.toPandas()
    after = profiling.phase_stats()[telemetry.SPAN_ROW_ASSEMBLY]
    assert mid - before in (1, 3, 4, 5, 6)
    assert after["count"] == mid + 1 and after["total_s"] > 0
    assert made == []


def test_collect_and_to_pandas_identical_with_and_without_a_scope():
    from sparkdl_tpu.core import telemetry

    plain_rows = _mapped_df().collect()
    plain_frame = _mapped_df().toPandas()
    with telemetry.Telemetry():
        traced_rows = _mapped_df().collect()
        traced_frame = _mapped_df().toPandas()
    assert traced_rows == plain_rows
    pd.testing.assert_frame_equal(traced_frame, plain_frame,
                                  check_exact=True)


# ---------------------------------------------------------------------------
# collect() assembles its rows column by column (PR 31): vector columns
# through numpy, the rest through Arrow — the rows are Table.to_pylist()'s,
# cell types included
# ---------------------------------------------------------------------------

_INTS = [pa.int8(), pa.int16(), pa.int32(), pa.int64(),
         pa.uint8(), pa.uint16(), pa.uint32(), pa.uint64()]


def _frame(columns, parts=1):
    return DataFrame.fromArrow(pa.table(columns), numPartitions=parts)


def _int_extremes(t):
    info = np.iinfo(t.to_pandas_dtype())
    return _frame({"v": pa.array([[info.min, 0, info.max], [1], None],
                                 pa.list_(t))})


def _sliced_chunks():
    # partitions that are slices of a longer batch: each chunk has a
    # non-zero offset and its list offsets do not start at 0
    batch = pa.record_batch({
        "i": pa.array(range(10)),
        "v": pa.array([[float(i)] * (i % 3) if i != 4 else None
                       for i in range(10)], pa.list_(pa.float32())),
        "f": pa.array([[i, i + 1] if i != 7 else None for i in range(10)],
                      pa.list_(pa.int16(), 2)),
        "w": pa.array([[i + 0.5] * 3 for i in range(10)],
                      pa.large_list(pa.float64()))})
    return DataFrame([batch.slice(2, 3), batch.slice(5, 5)], batch.schema)


def _null_rows_with_values_under_them():
    # a null row whose slot still covers values: flatten() must skip them
    v = pa.ListArray.from_arrays(
        pa.array([0, 2, 4, 6, 8], pa.int32()),
        pa.array(np.arange(8, dtype=np.float32)),
        mask=pa.array([False, True, False, False]))
    return _frame({"v": v})


def _image_struct_frame():
    from sparkdl_tpu.image import imageIO

    pixels = np.arange(3 * 4 * 5 * 3, dtype=np.uint8).reshape(3, 4, 5, 3)
    return _frame({
        "image": imageIO.imageArraysToStructColumn(
            pixels, [f"mem://{i}" for i in range(3)]),
        "features": pa.array([[0.5, 1.5], None, [2.5, 3.5]],
                             pa.list_(pa.float32()))})


def _duplicated_name():
    batch = pa.RecordBatch.from_arrays(
        [pa.array([[1.0], [2.0]], pa.list_(pa.float32())),
         pa.array(["x", "y"]),
         pa.array([[3.0], [4.0]], pa.list_(pa.float32()))],
        names=["a", "b", "a"])
    return DataFrame([batch], batch.schema)


# case → (frame, columns that go through numpy whole)
_COLLECT_CASES = {
    "list_float32": lambda: (_frame({"v": pa.array(
        [[0.1, 0.2, 0.3], [1e-45, 3.4e38, -1.5]], pa.list_(pa.float32()))}),
        1),
    "list_float64": lambda: (_frame({"v": pa.array(
        [[0.1, 1e308], [5e-324, -2.5]], pa.list_(pa.float64()))}), 1),
    "float_specials": lambda: (_frame({
        "a": pa.array([[float("nan"), float("inf"), -float("inf"), -0.0,
                        0.0]], pa.list_(pa.float32())),
        "b": pa.array([[float("nan"), float("inf"), -float("inf"), -0.0,
                        0.0]], pa.list_(pa.float64()))}), 2),
    "large_list": lambda: (_frame({"v": pa.array(
        [[1.0, 2.0], [3.0, 4.0]], pa.large_list(pa.float32()))}), 1),
    "fixed_size_list": lambda: (_frame({"v": fixed_size_list_array(
        np.arange(12, dtype=np.float32).reshape(4, 3))}), 1),
    "fixed_size_list_null_rows": lambda: (_frame({"v": pa.array(
        [[1, 2], None, [3, 4], None], pa.list_(pa.int32(), 2))}), 1),
    **{f"list_{t}": (lambda t=t: (_int_extremes(t), 1)) for t in _INTS},
    "null_rows": lambda: (_frame({"v": pa.array(
        [None, [1.0, 2.0], None, [3.0, 4.0], [5.0, 6.0], None],
        pa.list_(pa.float32()))}), 1),
    "null_rows_with_values_under_them": lambda: (
        _null_rows_with_values_under_them(), 1),
    "all_null_column": lambda: (_frame({"v": pa.array(
        [None, None, None], pa.list_(pa.float32()))}), 1),
    "ragged": lambda: (_frame({"v": pa.array(
        [[1.0], [2.0, 3.0, 4.0], None, [5.0, 6.0]],
        pa.list_(pa.float64()))}), 1),
    "zero_length_lists": lambda: (_frame({
        "some": pa.array([[], [1], []], pa.list_(pa.int64())),
        "all": pa.array([[], [], []], pa.list_(pa.float32()))}), 2),
    "sliced_chunks": lambda: (_sliced_chunks(), 3),
    "limit_and_filter": lambda: (_frame({
        "i": pa.array(range(12)),
        "v": pa.array([[float(i), -float(i)] for i in range(12)],
                      pa.list_(pa.float32()))}, parts=2)
        .filter(lambda i: i % 3 != 0, ["i"]).limit(5), 1),
    "several_partitions": lambda: (_frame({
        "i": pa.array(range(11)),
        "v": pa.array([[i, i * i] for i in range(11)],
                      pa.list_(pa.int32()))}, parts=4), 1),
    "empty_frame": lambda: (_frame({
        "i": pa.array([], pa.int64()),
        "v": pa.array([], pa.list_(pa.float32()))}), 1),
    "inner_nulls": lambda: (_frame({"v": pa.array(
        [[1.0, None], [2.0, 3.0]], pa.list_(pa.float32()))}), 0),
    "inner_nulls_in_one_partition": lambda: (_frame({"v": pa.array(
        [[1.0, 2.0], [3.0, 4.0], [5.0, None], [6.0, 7.0]],
        pa.list_(pa.float32()))}, parts=2), 0),
    "list_float16": lambda: (_frame({"v": pa.array(
        [np.array([1.5, 2.5], np.float16)], pa.list_(pa.float16()))}), 0),
    "list_bool": lambda: (_frame({"v": pa.array(
        [[True, False], [False]], pa.list_(pa.bool_()))}), 0),
    "list_list_float": lambda: (_frame({"v": pa.array(
        [[[1.0], [2.0, 3.0]], [[4.0]]],
        pa.list_(pa.list_(pa.float32())))}), 0),
    "list_string": lambda: (_frame({"v": pa.array(
        [["a", "b"], None, []], pa.list_(pa.string()))}), 0),
    "scalars_strings_timestamps": lambda: (_frame({
        "i": pa.array([1, None, 3]),
        "s": pa.array(["a", None, "c"]),
        "b": pa.array([b"\x00\x01", b"", None], pa.binary()),
        "t": pa.array([0, 1_000_000, None], pa.timestamp("us")),
        "m": pa.array([[("k", 1)], [], None],
                      pa.map_(pa.string(), pa.int32()))}), 0),
    "struct_with_binary_child": lambda: (_image_struct_frame(), 1),
    "duplicated_column_name": lambda: (_duplicated_name(), 2),
}


def _assert_same_cells(got, want, where="rows"):
    assert type(got) is type(want), where
    if isinstance(want, dict):
        assert list(got) == list(want), where
        for key in want:
            _assert_same_cells(got[key], want[key], f"{where}[{key!r}]")
    elif isinstance(want, (list, tuple)):
        assert len(got) == len(want), where
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_same_cells(g, w, f"{where}[{i}]")
    elif isinstance(want, float):
        assert repr(got) == repr(want), where   # NaN, ±inf, −0.0
    else:
        assert got == want, where


@pytest.mark.parametrize("case", list(_COLLECT_CASES))
def test_collect_is_to_pylist_cell_types_included(case):
    from sparkdl_tpu.core import telemetry

    df, vector_columns = _COLLECT_CASES[case]()
    table = df.toArrow()
    with telemetry.Telemetry() as tel:
        rows = df.collect()
    _assert_same_cells(rows, table.to_pylist())
    (span,) = tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY)
    assert span["attributes"]["vector_columns"] == vector_columns
    assert span["attributes"]["fallback_columns"] == \
        table.num_columns - vector_columns


def test_collect_counts_the_values_numpy_converted():
    from sparkdl_tpu.core import telemetry

    def counted(df):
        with telemetry.Telemetry() as tel:
            df.collect()
        return tel.metrics.snapshot()["counters"].get(
            telemetry.M_COLLECT_VECTORIZED_VALUES, 0)

    vectors = _frame({
        "i": pa.array(range(6)),
        "v": pa.array([[1.0, 2.0, 3.0]] * 4 + [None, [4.0]],
                      pa.list_(pa.float32()))}, parts=3)
    assert counted(vectors) == 13
    assert counted(make_df(10, 3)) == 0     # no vector column
    # a chunk with a null value inside a row goes to Arrow whole; the
    # column's other chunk still goes through numpy and is counted
    mixed = _COLLECT_CASES["inner_nulls_in_one_partition"]()[0]
    assert counted(mixed) == 4


# ---------------------------------------------------------------------------
# collect() assembles a partition's rows as soon as the partition has
# resolved, under the later partitions' work (PR 34). The tests make the
# partitions resolve in a chosen order, each only once collect() has been
# handed the one before it, so what is assembled early is decided and not
# left to the threads
# ---------------------------------------------------------------------------


@pytest.fixture
def in_order(monkeypatch):
    """``in_order(frame, order)``: ``frame`` with a pass-through op that
    holds partition ``order[k + 1]`` back until ``run_all`` has handed
    partition ``order[k]`` to its ``on_result`` — so all but ``order[-1]``
    are handed over while something is unresolved, and ``order[-1]``, which
    resolves last, never is."""
    from sparkdl_tpu.engine.supervisor import PartitionSupervisor

    monkeypatch.setattr(EngineConfig, "max_workers", 8)
    gates = {}      # id(first batch of the frame) → (gate by partition, next)
    real = PartitionSupervisor.run_all

    def run_all(sup, runners, on_result=None, on_idle=None):
        def handed(index, result):
            on_result(index, result)
            for events, after in gates.values():
                if index in after:
                    events[after[index]].set()
        return real(sup, runners, handed if on_result else None, on_idle)

    monkeypatch.setattr(PartitionSupervisor, "run_all", run_all)

    def make(frame, order, op=lambda index, batch: batch):
        parts = frame._partitions
        order = list(order)
        assert not frame._ops and sorted(order) == list(range(len(parts)))
        events = {i: threading.Event() for i in order}
        if order:
            events[order[0]].set()
        gates[id(events)] = (events, dict(zip(order, order[1:])))

        def held(batch):
            index = next((i for i, p in enumerate(parts) if p is batch),
                         None)      # None: a quarantine probe's empty slice
            if index is not None:
                assert events[index].wait(20), "the gate never opened"
            return op(index, batch)

        return frame.mapPartitions(held)

    yield make
    for events, _ in gates.values():
        for event in events.values():
            event.set()


def _collect_traced(df):
    """``df.collect()`` under a scope: rows, the row-assembly spans in the
    order they were opened, the overlapped-rows counter (None: not bumped)."""
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.engine import dataframe

    with telemetry.Telemetry() as tel:
        rows = df.collect()
    spans = sorted(tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY),
                   key=lambda s: s["start_ns"])
    counter = tel.metrics.snapshot()["counters"].get(
        dataframe.M_COLLECT_OVERLAPPED_ROWS)
    return rows, spans, counter


_OWN_PARTITIONS = ["sliced_chunks", "several_partitions",
                   "inner_nulls_in_one_partition", "duplicated_column_name"]


@pytest.mark.parametrize(
    "case,parts",
    [(case, parts) for case in _COLLECT_CASES for parts in (1, 2, 4)]
    + [(case, "own") for case in _OWN_PARTITIONS])
def test_collect_with_ops_is_to_pylist_whatever_the_partitions(
        case, parts, in_order):
    table = _COLLECT_CASES[case]()[0].toArrow()
    if parts == "own":  # the case's own batches: slices, offsets and all
        base = DataFrame(table.to_batches(), table.schema)
    else:
        base = DataFrame.fromArrow(table, numPartitions=parts)
    n = base.numPartitions
    sizes = [b.num_rows for b in base._partitions]
    # backwards: the first partition resolves last
    df = in_order(base, reversed(range(n)))
    rows, spans, counter = _collect_traced(df)
    _assert_same_cells(rows, table.to_pylist())
    _assert_same_cells(rows, df.toArrow().to_pylist())
    if n == 1:      # nothing to overlap with: the whole-table path
        (span,) = spans
        assert "partition" not in span["attributes"] and counter is None
    else:
        assert [s["attributes"]["partition"] for s in spans] == \
            list(reversed(range(n)))
        assert [s["attributes"]["rows"] for s in spans] == sizes[::-1]
        assert counter == sum(sizes) - sizes[0]
    # a column goes through numpy whole in the table where it does in
    # every partition
    assert min(s["attributes"]["vector_columns"] for s in spans) == \
        _COLLECT_CASES[case]()[1]


def _uneven_frame():
    """Four partitions of 1, 2, 3 and 4 rows with a vector column."""
    batches = []
    start = 0
    for size in (1, 2, 3, 4):
        ids = list(range(start, start + size))
        batches.append(pa.record_batch({
            "i": pa.array(ids, pa.int64()),
            "v": pa.array([[float(i), i + 0.5] for i in ids],
                          pa.list_(pa.float32()))}))
        start += size
    return DataFrame(batches, batches[0].schema)


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 2, 1, 0), (2, 0, 3, 1),
                                   (1, 3, 0, 2)])
def test_collect_rows_come_back_in_partition_order(order, in_order):
    df = in_order(_uneven_frame(), order)
    rows, spans, counter = _collect_traced(df)
    assert [r["i"] for r in rows] == list(range(10))
    _assert_same_cells(rows, df.toArrow().to_pylist())
    # assembled in the order they resolved; the last one after the run
    assert [s["attributes"]["partition"] for s in spans] == list(order)
    for span in spans:
        assert span["attributes"]["rows"] == \
            span["attributes"]["partition"] + 1
        assert span["attributes"]["to"] == "pylist"
        assert span["attributes"]["vector_columns"] == 1
        assert span["attributes"]["fallback_columns"] == 1
    # the counter: every row but those of the last partition to resolve
    assert counter == 10 - (order[-1] + 1)


@pytest.mark.parametrize("odd", [0, 1, 2, 3])
def test_collect_falls_back_to_the_table_on_another_schema(odd, in_order):
    # partition `odd` comes back with v as float64 where int64 is declared:
    # toArrow() unifies and casts, so the other partitions' cells change
    # type too, and nothing assembled from a batch alone may be kept.
    # Resolving order 2, 0, 3, 1: the odd one is met early (first, second,
    # third) or after the run (1, the last to resolve)
    def op(index, batch):
        if index != odd:
            return batch
        return batch.set_column(
            1, "v", batch.column("v").cast(pa.list_(pa.float64())))

    base = DataFrame.fromArrow(pa.table({
        "i": pa.array(range(8)),
        "v": pa.array([[i, -i] for i in range(8)], pa.list_(pa.int64()))}),
        numPartitions=4)
    df = in_order(base, (2, 0, 3, 1), op)
    rows, spans, counter = _collect_traced(df)
    want = df.toArrow().to_pylist()
    _assert_same_cells(rows, want)
    assert {type(v) for r in rows for v in r["v"]} == {float}
    whole = spans[-1]["attributes"]
    assert whole["rows"] == 8 and "partition" not in whole
    # what was assembled before the odd batch showed is in the trace, and
    # is not counted as rows collect() gave back
    assert [s["attributes"]["partition"] for s in spans[:-1]] == \
        list((2, 0, 3, 1)[:(2, 0, 3, 1).index(odd)])
    assert counter is None


def test_collect_failing_partition_raises_and_keeps_nothing(in_order):
    seen = []

    def op(index, batch):
        seen.append(index)
        if index == 3:
            raise ValueError("deliberate")
        return batch

    df = in_order(_uneven_frame(), (0, 1, 2, 3), op)
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.engine import dataframe

    with telemetry.Telemetry() as tel:
        with pytest.raises(TaskFailure, match="deliberate") as failure:
            df.collect()
    assert failure.value.index == 3
    assert df._materialized is None
    # the other partitions were assembled before 3 failed; their rows went
    # with the failure and are not counted
    assert sorted(s["attributes"]["partition"] for s in
                  tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY)) == [0, 1, 2]
    assert dataframe.M_COLLECT_OVERLAPPED_ROWS not in \
        tel.metrics.snapshot()["counters"]
    assert sorted(seen) == [0, 1, 2, 3]


def test_collect_then_to_arrow_runs_each_partition_once(in_order):
    seen = []
    df = in_order(_uneven_frame(), (1, 0, 3, 2),
                  lambda index, batch: seen.append(index) or batch)
    rows = df.collect()
    table = df.toArrow()
    assert df.collect() == rows == table.to_pylist()
    assert df.count() == 10 and sorted(seen) == [0, 1, 2, 3]


def test_collect_quarantined_partition_is_assembled_after_the_run(
        in_order, monkeypatch):
    monkeypatch.setattr(EngineConfig, "quarantine", True)

    def op(index, batch):
        if index == 1:
            raise ValueError("poison")
        return batch

    df = in_order(_uneven_frame(), (0, 2, 3, 1), op)
    rows, spans, counter = _collect_traced(df)
    assert [r["i"] for r in rows] == [0, 3, 4, 5, 6, 7, 8, 9]
    _assert_same_cells(rows, df.toArrow().to_pylist())
    # the others early; the stand-in is no task's result: after the run
    assert [(s["attributes"]["partition"], s["attributes"]["rows"])
            for s in spans] == [(0, 1), (2, 3), (3, 4), (1, 0)]
    assert counter == 8


@pytest.mark.parametrize("how", ["no_ops", "materialized", "one_partition",
                                 "durable"])
def test_collect_takes_the_table_path_where_nothing_runs_beside_it(
        how, tmp_path, monkeypatch):
    df = _uneven_frame()
    if how == "one_partition":
        df = df.repartition(1)
    if how != "no_ops":
        df = df.mapPartitions(lambda batch: batch)
    if how == "materialized":
        df.toArrow()
    if how == "durable":
        monkeypatch.setattr(EngineConfig, "durable_dir", str(tmp_path))
    rows, spans, counter = _collect_traced(df)
    _assert_same_cells(rows, df.toArrow().to_pylist())
    (span,) = spans
    assert span["attributes"]["rows"] == 10
    assert "partition" not in span["attributes"] and counter is None
    if how == "durable":
        assert os.listdir(tmp_path)     # the journal was written


def test_nested_collect_from_a_partition_thread_runs_inline(in_order):
    from sparkdl_tpu.core import telemetry

    inner_rows = {}

    def op(index, batch):
        inner = _uneven_frame().mapPartitions(lambda b: b)
        assert threading.current_thread().name.startswith("sparkdl-part")
        inner_rows[index] = inner.collect()
        return batch

    df = in_order(_uneven_frame(), (3, 2, 1, 0), op)
    with telemetry.Telemetry() as tel:
        rows = df.collect()
    assert [r["i"] for r in rows] == list(range(10))
    assert all(inner_rows[i] == rows for i in range(4))
    spans = tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY)
    # four inner collects over the whole table, four outer partitions
    assert sorted(s["attributes"].get("partition", -1) for s in spans) == \
        [-1, -1, -1, -1, 0, 1, 2, 3]


# ---------------------------------------------------------------------------
# collect() builds the cells of the columns the ops carry through from the
# source partitions unchanged while the partitions' tasks run, and converts
# only the computed columns once a partition resolves. The tests hold every
# partition back until collect() has built all it builds ahead, so which
# rows were built ahead is decided and not left to the threads
# ---------------------------------------------------------------------------


@pytest.fixture
def built_first(monkeypatch):
    """``built_first(frame)``: ``frame`` with a pass-through op that holds
    each partition back until ``collect()`` has nothing left to build ahead
    (at once where it builds nothing ahead)."""
    from sparkdl_tpu.engine import dataframe

    monkeypatch.setattr(EngineConfig, "max_workers", 8)
    done = threading.Event()
    plan, step = dataframe._CarriedRows.plan, dataframe._CarriedRows.step

    def planned(partitions, schema):
        carried = plan(partitions, schema)
        if carried is None:
            done.set()
        return carried

    def stepped(self, finished):
        more = step(self, finished)
        if not more:
            done.set()
        return more

    monkeypatch.setattr(dataframe._CarriedRows, "plan",
                        staticmethod(planned))
    monkeypatch.setattr(dataframe._CarriedRows, "step", stepped)

    def held(batch):
        assert done.wait(20), "collect() never finished building ahead"
        return batch

    yield lambda frame: frame.mapPartitions(held)
    done.set()


def _collect_carried(df):
    """``_collect_traced`` with the spans built ahead apart and the
    carried-rows counter (None: not bumped)."""
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.engine import dataframe

    with telemetry.Telemetry() as tel:
        rows = df.collect()
    spans = sorted(tel.tracer.spans(telemetry.SPAN_ROW_ASSEMBLY),
                   key=lambda s: s["start_ns"])
    ahead = [s for s in spans if s["attributes"].get("ahead")]
    counters = tel.metrics.snapshot()["counters"]
    return (rows, ahead, [s for s in spans if s not in ahead],
            counters.get(dataframe.M_COLLECT_CARRIED_ROWS),
            counters.get(telemetry.M_COLLECT_VECTORIZED_VALUES))


def _carried_table():
    """Carried columns of every kind collect() converts: an image struct
    with a binary child (null rows among them), a vector, a string and an
    all-null column."""
    from sparkdl_tpu.image import imageIO

    n = 150
    pixels = np.arange(n * 4 * 5 * 3, dtype=np.uint32).astype(
        np.uint8).reshape(n, 4, 5, 3)
    image = imageIO.imageArraysToStructColumn(
        pixels, [f"mem://{i}" for i in range(n)])
    image = pa.StructArray.from_arrays(
        image.flatten(), fields=list(image.type),
        mask=pa.array([i % 11 == 3 for i in range(n)]))
    return pa.table({
        "image": image,
        "v": pa.array([None if i % 7 == 0 else [i / 3, -i] for i in range(n)],
                      pa.list_(pa.float32())),
        "s": pa.array([None if i % 5 == 0 else f"row {i}" for i in range(n)]),
        "z": pa.array([None] * n, pa.list_(pa.float64()))})


def _features(batch):
    """A computed vector column: two floats a row, null every ninth."""
    rows = batch.column(1).to_pylist()
    return pa.array([None if i % 9 == 4 else [float(i), 0.5]
                     for i in range(len(rows))], pa.list_(pa.float32()))


@pytest.mark.parametrize("parts", [1, 2, 4, "own"])
def test_collect_with_carried_columns_is_to_pylist(parts, built_first):
    table = _carried_table()
    if parts == "own":  # slices of one batch, offsets and all
        batch = table.combine_chunks().to_batches()[0]
        base = DataFrame([batch.slice(0, 70), batch.slice(70, 80)],
                         batch.schema)
    else:
        base = DataFrame.fromArrow(table, numPartitions=parts)
    sizes = [b.num_rows for b in base._partitions]
    df = built_first(base).withColumnBatch("features", _features,
                                           pa.list_(pa.float32()))
    rows, ahead, spans, carried, values = _collect_carried(df)
    _assert_same_cells(rows, df.toArrow().to_pylist())
    assert [list(r) for r in rows[:1]] == [["image", "v", "s", "z",
                                            "features"]]
    # every row's carried cells were built ahead, in slices of 64 rows,
    # partition after partition, and only the computed column was left
    assert carried == len(rows) == sum(sizes)
    assert [(s["attributes"]["partition"], s["attributes"]["rows"])
            for s in ahead] == [
        (i, min(64, size - start)) for i, size in enumerate(sizes)
        for start in range(0, size, 64)]
    assert sorted(s["attributes"]["partition"] for s in spans) == \
        list(range(len(sizes)))
    assert {s["attributes"]["vector_columns"] for s in spans} == {1}
    # numpy's values: the carried vector's, built ahead, and the computed
    assert values == 2 * sum(r["v"] is not None for r in rows) + \
        2 * sum(r["features"] is not None for r in rows)


def test_collect_takes_no_column_an_op_replaced(built_first):
    # `v` is replaced under its name and type: the declared schema cannot
    # tell, the result's buffers do
    base = DataFrame.fromArrow(_carried_table(), numPartitions=3)
    df = built_first(base).withColumnBatch(
        "v", lambda b: pa.array([[1.0, 2.0]] * b.num_rows,
                                pa.list_(pa.float32())),
        pa.list_(pa.float32())).withColumnBatch(
        "features", _features, pa.list_(pa.float32()))
    rows, ahead, spans, carried, _ = _collect_carried(df)
    _assert_same_cells(rows, df.toArrow().to_pylist())
    assert {tuple(r["v"]) for r in rows} == {(1.0, 2.0)}
    assert ahead and carried == 0
    # every partition assembled from its result alone, as before
    assert [s["attributes"]["rows"] for s in spans] == [50, 50, 50]
    assert {s["attributes"]["fallback_columns"] +
            s["attributes"]["vector_columns"] for s in spans} == {5}


def test_collect_with_a_computed_column_in_the_middle_falls_back(
        built_first):
    # x computed between the source's a and b: b is no longer where the
    # source had it, so nothing is built ahead
    base = DataFrame.fromArrow(pa.table({
        "a": pa.array(range(12)), "b": pa.array([f"b{i}" for i in range(12)])
    }), numPartitions=3)
    schema = pa.schema([("a", pa.int64()), ("x", pa.float64()),
                        ("b", pa.string())])

    def middle(batch):
        return pa.RecordBatch.from_arrays(
            [batch.column(0), pa.array([0.5] * batch.num_rows),
             batch.column(1)], schema=schema)

    df = built_first(base).mapPartitions(middle, schema)
    rows, ahead, spans, carried, _ = _collect_carried(df)
    _assert_same_cells(rows, df.toArrow().to_pylist())
    assert [list(r) for r in rows[:1]] == [["a", "x", "b"]]
    assert ahead == [] and carried is None


def test_collect_with_carried_columns_raises_what_a_partition_raised(
        built_first):
    def fail(batch):
        if batch.column(0)[0].as_py() == 4:
            raise ValueError("deliberate")
        return pa.array([1] * batch.num_rows)

    base = DataFrame.fromArrow(pa.table({"i": pa.array(range(12))}),
                               numPartitions=3)
    df = built_first(base).withColumnBatch("f", fail, pa.int64())
    with pytest.raises(TaskFailure, match="deliberate") as failure:
        df.collect()
    assert failure.value.index == 1 and df._materialized is None


def test_collect_with_carried_columns_falls_back_on_an_undeclared_type(
        built_first):
    # no outputType: the declared column is null-typed, every batch has
    # another type, and the rows are toArrow()'s unified table's
    base = DataFrame.fromArrow(_carried_table(), numPartitions=3)
    df = built_first(base).withColumnBatch("features", _features)
    rows, ahead, spans, carried, _ = _collect_carried(df)
    _assert_same_cells(rows, df.toArrow().to_pylist())
    (whole,) = [s for s in spans if "partition" not in s["attributes"]]
    assert whole["attributes"]["rows"] == 150 and carried is None


def test_two_collects_share_no_object(built_first):
    def objects(value, out):
        if isinstance(value, (dict, list, bytes)):
            out.append(value)
        for child in (value.values() if isinstance(value, dict) else
                      value if isinstance(value, list) else ()):
            objects(child, out)
        return out

    df = built_first(DataFrame.fromArrow(_carried_table(), numPartitions=2)
                     ).withColumnBatch("features", _features,
                                       pa.list_(pa.float32()))
    first = df.collect()
    fresh = built_first(DataFrame.fromArrow(
        _carried_table(), numPartitions=2)).withColumnBatch(
        "features", _features, pa.list_(pa.float32()))
    for second in (df.collect(), fresh.collect()):
        assert second == first
        mine = {id(o) for o in objects(first, [])}
        assert not mine & {id(o) for o in objects(second, [])}


def test_same_data_is_the_same_memory_and_not_equal_values():
    from sparkdl_tpu.engine.dataframe import _same_data

    column = _carried_table().combine_chunks().column("image").chunk(0)
    assert _same_data(column, column.slice(0))
    assert not _same_data(column, column.slice(1))
    assert not _same_data(column.slice(0, 10), column.slice(0, 11))
    assert not _same_data(column, pa.concat_arrays([column]))   # a copy
    # the struct's own buffers and offset alike, its children shifted
    shifted = pa.StructArray.from_arrays(
        [f.slice(1, len(column) - 1) for f in column.flatten()],
        fields=list(column.type))
    assert not _same_data(column.slice(0, len(column) - 1), shifted)
    offsets = pa.array([0, 1, 3], pa.int32())
    values = pa.array([1.0, 2.0, 3.0, 4.0], pa.float32())
    vector = pa.ListArray.from_arrays(offsets, values)
    assert _same_data(vector, pa.ListArray.from_arrays(offsets, values))
    assert not _same_data(vector, pa.ListArray.from_arrays(
        offsets, values.slice(1)))


_FIT_CELL_IMPORTS = """
import sparkdl_tpu
from sparkdl_tpu.models import registry
from sparkdl_tpu.train import Trainer
from sparkdl_tpu.train.metrics import MetricsLogger
from sparkdl_tpu.core import profiling, telemetry
"""


@pytest.mark.parametrize("imports,engine_loaded", [
    ("import sparkdl_tpu.engine\n"
     "from sparkdl_tpu.engine import DataFrame\n"
     "import pyarrow as pa\n"
     "df = DataFrame.fromArrow(pa.table({'i': [1, 2, 3]}), 2)"
     ".mapPartitions(lambda b: b)\n", True),
    (_FIT_CELL_IMPORTS, False)])
def test_engine_import_starts_no_thread_and_fit_never_imports_it(
        imports, engine_loaded):
    # the early assembly lives inside collect()'s call: importing the
    # engine or building a frame starts no thread and makes no pool, and
    # what benchmarks/drivers/fit.py imports does not reach the engine
    import subprocess
    import sys

    script = (
        "import sys, threading\n" + imports +
        "assert [t.name for t in threading.enumerate()] == ['MainThread'], "
        "threading.enumerate()\n"
        "engine = [m for m in sys.modules "
        "if m.startswith('sparkdl_tpu.engine')]\n"
        "assert bool(engine) is " + str(engine_loaded) + ", engine\n"
        "if engine:\n"
        "    from sparkdl_tpu.engine import dataframe\n"
        "    assert dataframe._pool is None\n")
    done = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
