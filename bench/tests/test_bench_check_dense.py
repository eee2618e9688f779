"""``correct`` on the HPC-tier, closed-loop cell, at a test size on the CPU:
a sound run passes; the control in the program's place and each planted
fault fail."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import benchfaults as bf

CELL = "mistral7b-hpc-api"       # the fixture maps it to a test-size model


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bf.tiny_root(tmp_path_factory.mktemp("dense"))


def test_sound_run_is_correct_and_its_control_is_not(root):
    res = bf.run(root, CELL)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    # the control: the reference put in the program's place, computed a
    # precision lower, judged by the same checks and limits
    ctl = bf.run(root, CELL, control=bf.CONTROL)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["failed"]["value"] == 0
    assert ctl["checks"]["stream_mismatch"]["value"] == 0


def test_decode_that_keeps_its_state_is_not_correct(root, monkeypatch):
    from repro.models.lm import TransformerLM
    bf.keep_state(monkeypatch, TransformerLM)
    res = bf.run(root, CELL)
    assert not res["correct"], res["checks"]


def test_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    bf.alter_tokens(monkeypatch)
    res = bf.run(root, CELL)
    assert not res["correct"], res["checks"]
