"""The toy regressor in plain numpy, float64, gradients written out by hand:
`num_hidden_layers` tanh layers, a linear output, mean squared error, SGD.
Imports nothing of the program."""

import numpy as np


def param_shapes(model):
    widths = [model["input_size"]] + [model["hidden_size"]] * model["num_hidden_layers"] + [1]
    return {f"w{i}": (cin, cout) for i, (cin, cout) in enumerate(zip(widths[:-1], widths[1:]))}


def train_steps(model, params, batches, lr):
    """Losses of one SGD step per batch, from `params`."""
    params = {k: np.asarray(v, np.float64) for k, v in params.items()}
    names = sorted(params, key=lambda k: int(k[1:]))
    losses = []
    for batch in batches:
        x, y = np.asarray(batch["x"], np.float64), np.asarray(batch["y"], np.float64)
        acts = [x]
        for name in names[:-1]:
            acts.append(np.tanh(acts[-1] @ params[name]))
        err = acts[-1] @ params[names[-1]] - y
        losses.append(float(np.mean(err**2)))
        grad = 2.0 * err / err.size
        for i in reversed(range(len(names))):
            g_w = acts[i].T @ grad
            if i:
                grad = (grad @ params[names[i]].T) * (1.0 - acts[i] ** 2)
            params[names[i]] = params[names[i]] - lr * g_w
    return losses
