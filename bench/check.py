"""The comparison that decides ``correct``.

Every answer that a run kept names its reference in its ``answer`` entry:
``bench/references/<reference>.py``, which gives

* ``answer(out)``: the collected output as the answer compared;
* ``expected(data, specs)``: the plain reference's answer to each
  ``answer`` entry, from the cell's data alone;
* ``control(data, specs)``: the control's answers (the reference with
  one stated guarantee broken), for ``bench/control.py`` and the tests;
* ``number(pairs)``: from ``(answer, expected)`` pairs, the one number
  compared, named ``NUMBER``, with its limit ``LIMIT``.

The answers are compared once the window has closed. Every run also
compares ``failed_actions``, the actions that raised: an answer that
never comes. The window starts at least one action, so a run with no
failed action has answers to compare.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Tuple

from bench import spec

Numbers = Dict[str, Tuple[int, int]]


def _key(answer_spec: Dict[str, Any]) -> str:
    return json.dumps(answer_spec, sort_keys=True)


def answer_specs(traffic: Any) -> List[Dict[str, Any]]:
    """Every distinct ``answer`` entry of a traffic file, in file order."""
    found: Dict[str, Dict[str, Any]] = {}

    def walk(v: Any) -> None:
        if isinstance(v, dict):
            for k, w in v.items():
                if k == "answer" and isinstance(w, dict):
                    found.setdefault(_key(w), w)
                else:
                    walk(w)
        elif isinstance(v, list):
            for w in v:
                walk(w)

    walk(traffic)
    return list(found.values())


def compare(actions, data) -> Numbers:
    """The numbers compared, each with its limit."""
    numbers: Numbers = {
        "failed_actions": (sum(a.error is not None for a in actions), 0)}
    groups: Dict[str, list] = {}
    for a in actions:
        if a.answer is not None:
            groups.setdefault(a.answer_spec["reference"], []).append(a)
    for name, acts in groups.items():
        ref = spec.module("references", name)
        specs = {_key(a.answer_spec): a.answer_spec for a in acts}
        want = dict(zip(specs, ref.expected(data, list(specs.values()))))
        numbers[ref.NUMBER] = (
            ref.number([(a.answer, want[_key(a.answer_spec)])
                        for a in acts]), ref.LIMIT)
    return numbers


def is_correct(numbers: Numbers) -> bool:
    return all(v <= lim for v, lim in numbers.values())
