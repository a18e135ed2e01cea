"""Request kinds, one file per ``kind`` of a traffic mix.

Each module provides ``setup(cell, seed)``, ``payload(state, index)``,
``request(state, payload, span, traced)`` returning ``(answer, work)``,
``warmup(state, span)``, ``check(state, answers, seed)`` returning the
numbers compared with the cell's limits, and ``control(state, answers,
seed)`` returning the same numbers with the reference at a lower precision
in the program's place.
"""
