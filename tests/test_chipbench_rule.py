"""Tier-1 runs the rule that decides a serving cell's ``correct``: the four
statistics of the served-logit gap, which of them a cell's file has to name
(``serve.limit_problems``; the 99th percentile in the maximum's place where
a family declares ``DISCRETE_CHOICES``, as ``glm_dsa`` does), every serving
cell's own file under it, and the routed twin that shows on the CPU what the
rule is for. The cases are ``chipbench/tests/test_discrete_choices.py``'s,
imported as they stand (PERF.md section 7 asked a PR outside ``chipbench/``
for this), with the new family's cell among the cells they list."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:       # chipbench lies beside tests/, at the root
    sys.path.insert(0, ROOT)

from chipbench.tests.test_discrete_choices import *  # noqa: E402,F401,F403
from chipbench.tests.test_discrete_choices import SERVING  # noqa: E402


def test_the_new_familys_cell_is_among_the_cells_the_rule_is_run_on():
    from chipbench import families, harness as H
    cell = next(w for w in SERVING if w["name"] == "glm5-longdoc-sessions")
    family = families.of(H.load_config(cell["config"], False))
    assert family.DISCRETE_CHOICES == ("router_topk", "indexer_topk")
    limits = H.load_json("cells", cell["name"] + ".json")["limits"]
    assert "served_logit_gap_p99" in limits
    assert "served_logit_gap" not in limits
