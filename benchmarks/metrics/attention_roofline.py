"""Scope `attention`'s share of its roofline in the traced generation
(`_scope_roofline.py`)."""

import _scope_roofline


def read(run):
    return _scope_roofline.read(run, "attention")
