"""K-FAC-friendly modules: recurrent (reference kfac/modules) and
stacked experts."""

from distributed_kfac_pytorch_tpu.modules.experts import ExpertsDense

from distributed_kfac_pytorch_tpu.modules.lstm import (
    LSTM,
    LSTMCell,
    LSTMCellKFAC,
    LSTMLayer,
)
