"""Halo elements the padded all-to-all moves per iteration over those the
partition needs (``GraphEngine.stats``, program counters)."""


def read(run):
    st = run.job.stats
    if not st.true_halo_messages_per_iter:
        return None
    return st.padded_halo_elements_per_iter / st.true_halo_messages_per_iter
