"""Nemotron-3-Nano's wider prefill buckets, compiled for a described
`v5e:2x2`: tests/test_tpu_compile_nemotron.py's check at the rungs over 2,048,
every one a riding rung's program, in a file of their own (`--dist loadfile`
keeps a file on one worker)."""

import pytest

from test_tpu_compile_nemotron import (
    nemotron_program_keeps_pages_and_state_in_place)

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


@pytest.mark.timeout(300)
@pytest.mark.parametrize("program", ["prefill2560", "prefill3072",
                                     "prefill3584", "prefill4096"])
def test_nemotron_programs_keep_pages_and_state_in_place_on_v5e(
        topo, program, monkeypatch):
    nemotron_program_keeps_pages_and_state_in_place(topo, program,
                                                     monkeypatch)
