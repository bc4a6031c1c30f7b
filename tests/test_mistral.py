"""The dense kind on the serving path (benchmark/models/mistral.py: grouped-
query attention with rotary positions, SwiGLU): the contract every served
kind passes, on the tiny configuration of ``benchmark/tests/tiny``, 2 layers
of hidden 64, seeded random weights, on the CPU.  What the dense cache and
the engine do besides is ``tests/test_llm.py``'s and
``tests/test_prefill_rows.py``'s; numbers here are about results, never
speed."""

import contract
import kinds


class TestMistral(contract.ServedKind):
    row = kinds.KINDS["mistral"]
