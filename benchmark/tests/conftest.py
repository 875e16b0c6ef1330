"""The fixture architecture `tinymoe` (fixtures/tinymoe/, PR 26) was written
against ops/moe.py's capacity dispatch, and its reference writes out
`capacity_factor` 1.25's drops. Since PR 27 the program drops no token, and
neither does that reference where its CAPACITY_FACTOR is num_experts /
num_experts_per_tok (an expert's capacity is then every token of the call).
It is set here, for the tests of this directory that put the fixture on the
path, because the PR that took the capacity out of the program may add files
under benchmark/ and edit none. A `benchmark` PR writes it into
`reference_tinymoe.py` (or takes the capacity out of it) and deletes this file.
"""

import importlib

import pytest


@pytest.fixture(autouse=True)
def tinymoe_reference_drops_nothing(request, monkeypatch):
    if "tinymoe_on_path" not in request.fixturenames:
        return
    request.getfixturevalue("tinymoe_on_path")
    widths = importlib.import_module("benchmark.models.tinymoe").REHEARSE
    monkeypatch.setattr(
        importlib.import_module("benchmark.reference_tinymoe"),
        "CAPACITY_FACTOR",
        widths["num_experts"] / widths["num_experts_per_tok"])
