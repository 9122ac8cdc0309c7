"""The paper's sensor field (``field.make_field``): sensors uniform in the
unit square with thresholded Gaussian weights, drawn once from the
configuration's ``field_seed`` at its ``n_vertices``, ``kappa`` and
``sigma``."""

import field


def make(config):
    return field.make_field(config["field_seed"], config["n_vertices"], config["kappa"],
                            config["sigma"])
