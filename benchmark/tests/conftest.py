"""Holds nothing any more. Until PR 34 ``test_contract.py`` compared its
``FLAGS`` with the manifest's cells by ``==``, and a cell added later
recorded its flags here; that comparison is ``<=`` now and every cell's
flags are in ``test_contract.FLAGS``. The file stays only because
``tests/test_benchmark_contract.py`` imports it by name and a ``benchmark``
PR may edit no file outside ``benchmark/``: the next PR that may edit
``tests/`` drops that import and deletes this file (PERF.md section 7)."""
