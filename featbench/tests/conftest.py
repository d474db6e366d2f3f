"""The per-layer readers' test context of ``test_featbench_parts`` holds
the program's host spans and queue-wait histogram too, so that its check
that every listed metric reads a number covers their readers."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from program_spans import add_program_spans  # noqa: E402


@pytest.fixture(autouse=True)
def _ctx_with_program_spans(request, monkeypatch):
    mod = request.module
    if mod.__name__ != "test_featbench_parts":
        return
    base = mod._ctx
    monkeypatch.setattr(mod, "_ctx", lambda cell: add_program_spans(base(cell)))
