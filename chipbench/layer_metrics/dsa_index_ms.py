"""Time chip 0 spends a step under the scopes ``dsa_index`` and
``dsa_target`` of ``horovod_tpu/models/dsa.py``: what scoring the keys
(the indexer's three products, its norm and rotary, the index scores) and
training the scorer (the head-averaged probabilities made again, the KL
term and the indexer's gradients) cost; forward, recomputed and backward.
Left out where the program has no such scope."""
from chipbench.layer_metrics import dsa_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return dsa_ms.under(trace, (dsa_ms.INDEX, dsa_ms.TARGET))
