"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's ``foo.mpirun=4.input`` trick (SURVEY.md §4): the
reference exercises its MPI paths with oversubscribed local ranks; we
exercise our sharding paths with ``xla_force_host_platform_device_count``
virtual CPU devices. Real-TPU execution is covered by chip_smoke.py and
the described-chip compiles of tests/test_tpu_compile.py, not by this suite.

Must set env vars BEFORE jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Allow float64 in tests: production state is f32 (TPU), but convergence
# tests validate the SAME operators at f64 on CPU so truncation error is
# measured above the roundoff floor (SURVEY.md §7.3 hard-part #2).
jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    """A 1-D 8-device mesh for sharding tests."""
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8])
    return Mesh(devs, axis_names=("x",))


@pytest.fixture(scope="session")
def mesh2x4():
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, axis_names=("x", "y"))


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_state_per_module():
    """Clear jax's compilation caches after every test module.

    The monolithic full-gate run (650+ tests, one process) accumulates
    hundreds of compiled CPU executables; at ~45% of the round-5 suite
    XLA's CPU compiler segfaulted inside backend_compile_and_load —
    reproducibly, while every file passes in isolation (the split-gate
    receipt). Dropping the executables between modules bounds the
    in-process compiler/runtime state the monolithic run carries; each
    module re-compiles only its own shapes, so the wall-clock cost is
    minor."""
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Fast/slow test tiers (VERDICT round 2, item 8): the full suite is the
# pre-commit gate (~60 min on the virtual 8-device CPU mesh); the
# developer loop is `pytest -m "not slow"`. The tier is defined HERE
# (names measured >= ~12 s by `--durations`) so the policy lives in one
# place instead of scattered decorators.
# ---------------------------------------------------------------------------

SLOW_FILES = {
    "test_lagrangian_sharded.py",   # ~29 min total: sharded-marker suites
    "test_pallas_packed.py",        # Pallas interpret mode: ~3 min on CPU
}

SLOW_TESTS = {
    # PR 5 replay drills: end-to-end record -> escalate -> replay loops
    # (multiple jitted-run compiles each; the kill-and-replay drill
    # spawns a subprocess victim). Covered in CI by dryrun path 18.
    "test_precision_escalation_end_to_end_drill",
    "test_engine_override_verdict",
    "test_cross_mesh_kill_and_replay",
    "test_window_tracks_advected_membrane",
    "test_window_regrid_3d_smoke",
    "test_oldroyd_b_steady_shear_analytic",
    "test_elastic_disc_relaxes",
    "test_ib_shell3d_sharded_matches_single",
    "test_sharded_multilevel_matches_single_device",
    "test_membrane_in_refined_box_tracks_uniform_fine",
    "test_shell_step_fast_matches_scatter",
    "test_wall_bounded_ins_sharded_matches_single",
    "test_ib_membrane_sharded_matches_single",
    "test_two_level_ib_sharded_matches_single",
    "test_vc_poisson_3d",
    "test_straight_rod_zero_strain",
    "test_falling_drop_volume_and_symmetry",
    "test_fac_3d_smoke",
    "test_total_force_and_torque_balance",
    "test_intrinsic_curvature_equilibrium",
    "test_vortex_matches_uniform_fine",
    "test_profile_trace_writes_trace",
    # PR 10: real jax.profiler capture + attribute round trip (~30 s:
    # one jit compile, a 40-step captured run, and trace parsing)
    "test_real_capture_attributes_driver_chunk",
    # PR 19 gradient drills: end-to-end FD checks roll the coupled
    # solver out twice per direction at f64 (~5-7 s each). The fast
    # tier keeps the cheap spectral/interp FD checks and the census,
    # donation-guard, remat and design-loop pins; these two heavies
    # are covered in CI by dryrun path 23 (--design-smoke).
    "test_eel_objective_grad_matches_fd",
    "test_packed_spread_vjp_matches_fd",
    "test_gib_twisted_rod_relaxes",
    "test_project_vc_divergence_free",
    "test_3d_channel_smoke",
    "test_matches_scatter_path",
    "test_f32_convergence_regression",
    "test_adjointness",
    "test_two_level_matches_uniform_fine",
    "test_3d_channel_integrator_smoke",
    "test_imp_step_jits",
    "test_vc_projection_mg_preconditioner_ratio_robust",
    "test_lid_driven_cavity_re100_ghia",
    "test_preconditioner_iterations_bounded",
    "test_drop_buoyancy_relative_motion",
    "test_dirichlet_exact_inverse",
    "test_variable_coefficient_poisson",
    "test_exact_inverse_channel_unsteady",
    "test_implicit_midpoint_3x_matches_reference",
    "test_implicit_backward_euler_14x_matches_reference",
    "test_constant_field_interp_and_moment",
    "test_overflow_fallback_exact",
    "test_periodic_transverse_axis",
    "test_channel_develops_to_poiseuille",
    "test_constant_field_interpolates_exactly",
    "test_grid_independent_convergence",
    "test_hydrostatic_balance_no_spurious_currents",
    "test_three_level_tracks_uniform_fine_and_converges",
    "test_early_time_added_mass_free_fall",
    "test_vortex_3level_matches_uniform_finest",
    "test_membrane_ib_3level",
    "test_single_box_matches_two_level",
    "test_fac_multilevel_preconditioner",
    "test_cib_terminal_velocity_matches_constraint_ib",
    "test_preconditioner_cuts_iterations",
    "test_wave_generated_then_damped",
    "test_porous_obstacle_drag_balances_driving_force",
    "test_multilevel_ins_sharded_matches_single",
    "test_multilevel_regrid_tracks_drifting_structure",
    "test_channel_develops_to_poiseuille_stabilized_ppm",
    "test_two_level_ib_3d_shell",
    "test_two_level_ib_3d_sharded_matches_single",
    # round-3 re-tier (fast tier had grown to 27 min; --durations=50):
    "test_shell_silhouette_packing_efficiency",
    "test_chunk_capacity_overflow_exact",
    "test_free_body_two_bodies_interact",
    "test_two_level_conservation",
    "test_momentum_conservation_beats_nonconservative",
    "test_free_body_matches_direct_resistance_path",
    "test_ppm_reduces_to_centered_on_linear_field",
    "test_stabilized_ppm_free_stream_preservation",
    "test_hot_tile_takes_many_chunks_no_overflow",
    "test_vc_beta_folds_into_coefficient",
    "test_stokes_box_energy_decay",
    "test_free_body_step_advances",
    "test_conservative_3d_smoke",
    "test_multilevel_ib_3d_shell",
    "test_bf16_compute_matches_f32_within_tolerance",
    "test_hydrodynamic_force_measures_body_drag",
    "test_multilevel_ib_sharded_matches_single",
    # round-4 additions (measured >= ~12 s)
    "test_two_level_ib_sharded_window_matches_single",
    "test_two_level_ib_3d_sharded_window_matches_single",
    "test_multilevel_ib_sharded_boxes_matches_single",
    "test_nwt_physical_walls_match_brinkman",
    "test_free_body_trajectory_matches_constraint_ib",
    "test_explicit_composite_unstable_beyond_limit",
    "test_implicit_composite_stable_at_10x",
    "test_implicit_composite_matches_explicit_at_small_dt",
    "test_falling_drop_walled_tank_stable_and_conserves",
    "test_channel_viscous_mode_decay_rate",
    "test_conservative_walled_mass_exact",
    "test_komega_channel_law_of_the_wall",
    "test_vc_ins_sharded_matches_single",
    "test_smagorinsky_walled_channel_decays_bounded",
    "test_falling_drop_3d_walled_smoke",
    "test_hydrostatic_quiescence_3d_walled_tank",
    "test_komega_walled_transport_sane",
    "test_komega_ins_walled_channel_smoke",
    "test_ibfe_on_two_level_hierarchy_relaxes",
    "test_ibfe_two_level_matches_uniform_fine",
    "test_cylinder_wake_drag_re20",
    "test_ib_open_free_structure_advects",
    "test_implicit_regridding_window_tracks_structure",
    "test_two_level_ib_sharded_window_s2_markers_matches_single",
    "test_membrane_capsule_sediments_in_two_phase_tank",
    "test_open_ins_sharded_matches_single",
    "test_ib_open_sharded_matches_single",
    "test_fe_capsule_in_two_phase_fluid",
    "test_ib_open_3d_sphere_smoke",
    # round-5 additions
    "test_shedding_cylinder_adaptive_dt",
    "test_open_outlet_passes_throughflow",
    "test_open_outlet_wave_train_finite_and_bounded",
    "test_les_refined_window_matches_uniform_fine",
    "test_walled_cib_mobility_symmetric_and_confined",
    "test_walled_cib_wall_approach_monotonicity",
    "test_walled_cib_prescribed_kinematics_and_free_step",
    "test_vc_open_outlet_sharded_matches_single",
    "test_les_two_level_sharded_matches_single",
    "test_cib_walled_sharded_matches_single",
    "test_cross_mesh_restart_flagship_1_to_8_and_back",
    "test_filament_example_short",
    "test_oscillating_cylinder_example",
    "test_filament_length_conservation",
    "test_dam_break_example_short",
    "test_eel_example_swims_against_wave",
    "test_ibfe_beam_example_bends_downstream",
    "test_dam_break_restart_continuation",
    # PR 2 (resilience): subprocess SIGKILL drill spawns 4 interpreters
    "test_kill_mid_write_loses_at_most_one_interval",
    # PR 3 (silent failures): real-sleep stall drill — wall-clock
    # timing-sensitive, so it rides the slow tier, not the dev loop
    "test_watchdog_flags_stalled_supervised_run",
    # PR 6 (sharded checkpoints): subprocess kill drills — each spawns
    # multiple interpreters; covered in CI by dryrun path 19
    "test_sharded_kill_one_writer_loses_at_most_one_interval",
    "test_sharded_smoke_drill_end_to_end",
    # PR 6 re-tier (measured >= ~12 s by --durations on the
    # single-core tier-1 box; the fast tier had crept to within ~30 s
    # of the 870 s gate budget, so borderline runs timed out at ~93%
    # — the "environment-specific" tier-1 flake)
    # PR 7 (fleet): the subprocess drill spawns an interpreter for the
    # B=8 shell fleet (covered in CI by dryrun path 20); the capsule
    # test compiles two shell fleet chunks plus an unbatched replay
    "test_fleet_smoke_drill_end_to_end",
    "test_sliced_capsule_replays_bitwise",
    "test_open_outlet_hydrostatic_quiescence",
    "test_walled_momentum_wall_shear_sign",
    "test_hybrid_in_flagship_model",
    "test_failed_engine_degrades_and_matches_fallback",
    "test_hybrid_bf16_registry_name",
    # PR 17 (traffic): multi-minute sustained soaks (real-time open
    # loop; the bounded variants run in tier-1 via `slo.py check
    # --soak` and dryrun path 21)
    "test_soak_long_sustained_open_loop",
    "test_soak_long_chaos_smoke",
    # PR 18 (robustness): elastic-pool drills against a LIVE router
    # (real compiles, real-time open loop; the stub-router fast tier
    # covers the same policy logic in milliseconds, and CI exercises
    # the full drill via `slo.py check --elastic` and dryrun path 22)
    "test_grow_never_blocks_serving",
    "test_restart_drill_zero_fresh_compiles",
    "test_run_elastic_smoke_end_to_end",
    # PR 20 (assimilation): the collapse->rollback->escalation loop
    # compiles two fleet chunks + analysis executables; the subprocess
    # chaos drill spawns an interpreter (covered in CI by dryrun path
    # 24 and `slo.py check --assim`)
    "test_spread_collapse_rolls_back_and_escalates_inflation",
    "test_assim_smoke_drill_end_to_end",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy integrator/sharding tests; excluded from "
        "the developer fast tier (-m 'not slow')")


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.name.split("[")[0]
        if item.fspath.basename in SLOW_FILES or base in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
