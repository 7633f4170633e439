"""Operations the algorithm needs, counted from shapes.

The CNN's layers are read off its parameter shapes: a 3x3 SAME convolution
followed by a 2x2 pool halves the side, a dense layer is a matrix product.
One multiply-add is two operations.  A training step costs three forward
passes (forward, gradient of the activations, gradient of the weights)."""
from __future__ import annotations


def cnn_forward_flops(shapes: dict, side: int = 28) -> int:
    """Forward operations per sample.  ``shapes`` maps each layer to the
    shape of its weight: (kh, kw, cin, cout) for a convolution, (din, dout)
    for a dense layer, in the order the layers run."""
    total = 0
    for name, w in shapes.items():
        if len(w) == 4:
            kh, kw, cin, cout = w
            total += 2 * side * side * kh * kw * cin * cout
            side //= 2
        else:
            total += 2 * w[0] * w[1]
    return total


def cnn_train_flops(shapes: dict, side: int = 28) -> int:
    return 3 * cnn_forward_flops(shapes, side)


PAPER_CNN = {"conv1": (3, 3, 1, 8), "conv2": (3, 3, 8, 16),
             "fc1": (784, 128), "fc2": (128, 64), "fc3": (64, 10)}

