"""Operations and bytes of the grouped expert matmuls of a training step
(``expert_gmm`` and ``expert_tgmm``) over the held experts' routes.

Each held route runs the SwiGLU expert's three products (gate and up,
hidden -> width; down, width -> hidden) forward, for the input gradient
and for the weight gradient: 9 products of 2 x hidden x width operations
each. Bytes are what the algorithm has to move in float32: each product
reads its route's input row and writes its output row (hidden + width
elements), and each of a layer's invocations reads the held experts'
weights six times (forward and input gradient) and writes their
gradient three times.
"""


def cost(shape: dict) -> tuple:
    """``shape``: ``routes`` (held routes), ``invocations`` (expert
    layers run: stations x local steps x layers x rounds), ``hidden``,
    ``width`` and ``experts`` (held)."""
    d, f = shape["hidden"], shape["width"]
    flops = 9 * 2 * d * f * shape["routes"]
    nbytes = (9 * 4 * (d + f) * shape["routes"]
              + 9 * 4 * shape["experts"] * d * f * shape["invocations"])
    return flops, nbytes
