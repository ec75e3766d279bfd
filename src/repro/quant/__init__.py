"""BitNet-style quantization: QAT fake-quant + sub-byte packing."""
from repro.quant import bitnet, packing
from repro.quant.bitnet import (
    QuantizedTensor,
    TernaryWeight,
    bit_linear_serve,
    bit_linear_train,
    fake_quant_act,
    fake_quant_weight,
    pack_weight_ternary,
    quantize_act_int8,
    quantize_weight_ternary,
    ternary_dot,
    ternary_weight,
)
from repro.quant.packing import pack, pack_2bit, pack_4bit, unpack, unpack_2bit, unpack_4bit
