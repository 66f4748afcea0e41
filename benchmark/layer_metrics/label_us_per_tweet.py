"""Host time the labeler took per tweet published in the window: the
``featurize.label`` sub-spans of the program's span file (the label read
from the text, ``Featurizer._label_units`` around ``unit_label_fn``; its
own sub-stage since PR 32, no longer inside ``featurize.numeric``). A
program without the span (a learner whose label is a parsed field, or a
program from before PR 32) gives None."""


def read(art):
    spans, tweets = art.get("spans") or {}, art.get("tweets")
    if "featurize.label" not in spans or not tweets:
        return None
    return 1e3 * spans["featurize.label"]["total_ms"] / tweets
