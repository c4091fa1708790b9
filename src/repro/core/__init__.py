"""Core SFVI library — the paper's contribution as composable JAX modules."""
from repro.core.family import (
    FAMILIES,
    FamilySpec,
    VariationalFamily,
    build_family,
    family_names,
    get_family,
    register_family,
)
from repro.core.families import (
    BatchedDiagGaussian,
    CholeskyGaussian,
    ConditionalGaussian,
    DiagGaussian,
    LowRankGaussian,
)
from repro.core.model import StructuredModel, empty_theta
from repro.core.elbo import (elbo_objective, elbo_value, iwae_objective,
                             iwae_value, stl_objective)
from repro.core.sfvi import SFVIProblem
from repro.core.barycenter import (
    diag_barycenter,
    family_barycenter,
    gaussian_barycenter,
    gaussian_barycenter_cov,
    sqrtm_eigh,
    sqrtm_newton_schulz,
    wasserstein2_gaussian,
)

__all__ = [
    "FAMILIES",
    "FamilySpec",
    "VariationalFamily",
    "build_family",
    "family_names",
    "get_family",
    "register_family",
    "BatchedDiagGaussian",
    "CholeskyGaussian",
    "ConditionalGaussian",
    "DiagGaussian",
    "LowRankGaussian",
    "StructuredModel",
    "empty_theta",
    "elbo_objective",
    "elbo_value",
    "iwae_objective",
    "iwae_value",
    "stl_objective",
    "SFVIProblem",
    "diag_barycenter",
    "family_barycenter",
    "gaussian_barycenter",
    "gaussian_barycenter_cov",
    "sqrtm_eigh",
    "sqrtm_newton_schulz",
    "wasserstein2_gaussian",
]
