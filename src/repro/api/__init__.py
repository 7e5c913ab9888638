"""``repro.api`` — the declarative front door to the dataflow cost
model and every search engine behind it.

One query surface replaces the four historical entry points
(``mapspace.search``/``co_search``, ``netspace.search_network``/
``co_search_network`` — all still available as thin parity-tested
wrappers over this path):

    from repro.api import Query, Workload, Hardware, SearchSpec, Session

    s = Session()   # compile cache: $JAX_COMPILATION_CACHE_DIR, else
                    # <checkout>/.jax_cache

    # one layer, fixed hardware
    q = Query(Workload.of_layer(op), Hardware(num_pes=256, noc_bw=32.0),
              SearchSpec(objective="edp", budget=1000))
    report = s.run(q)
    print(report.best["value"], report.to_json())

    # a whole network; grid hardware turns a query into a co-DSE
    s.run(Query(Workload.of_network("vgg16")))

    # the headline: heterogeneous queries coalesced into one padded
    # device pass per (op-class, level-count) family
    reports = s.run_many([q1, q2, q3, q4, q5, q6])

See ``repro.launch.query`` for the CLI (single queries and
``--file queries.json`` batch mode).
"""
from .report import Report
from .session import (PendingReport, Session, default_session, run,
                      run_many)
from .spec import (OP_BUILDERS, SCHEMA_VERSION, Hardware, Query,
                   SearchSpec, Workload, op_from_json, queries_from_file,
                   select_layers)

__all__ = [
    "Hardware", "OP_BUILDERS", "PendingReport", "Query", "Report",
    "SCHEMA_VERSION", "SearchSpec", "Session", "Workload",
    "default_session", "op_from_json", "queries_from_file", "run",
    "run_many", "select_layers",
]
