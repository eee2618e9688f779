"""Seeded English-like prompt text: ten domains of topics under three
classes of question templates (the routing benchmark's query source,
copied here so that the yardstick owns it). Every template is ASCII,
so one character is one byte and one byte-level token."""

from __future__ import annotations

DOMAINS = {
    "hpc": ["MPI collectives", "SLURM job arrays", "GPU memory hierarchies",
            "parallel file systems", "InfiniBand networking"],
    "math": ["eigenvalue decompositions", "measure theory", "group homomorphisms",
             "partial differential equations", "convex duality"],
    "stats_ml": ["gradient descent", "variational inference", "random forests",
                 "attention mechanisms", "cross-validation"],
    "phys_chem": ["entropy", "molecular orbitals", "quantum tunnelling",
                  "reaction kinetics", "phase transitions"],
    "engineering": ["beam deflection", "control loops", "signal filtering",
                    "finite element methods", "thermal management"],
    "life_sci": ["CRISPR editing", "protein folding", "neural signalling",
                 "immune responses", "gene expression"],
    "cs_software": ["hash tables", "race conditions", "garbage collection",
                    "database indexing", "compiler optimization"],
    "philosophy": ["utilitarianism", "epistemic justification", "free will",
                   "the trolley problem", "moral realism"],
    "social_sci": ["survey sampling bias", "supply and demand", "social capital",
                   "voting systems", "urbanization"],
    "history": ["the printing press", "the silk road", "the industrial revolution",
                "ancient trade routes", "the space race"],
}

TEMPLATES = [
    "What is {topic}?",
    "Define {topic} in one sentence.",
    "Who first described {topic}?",
    "When was {topic} introduced?",
    "List three examples of {topic}.",
    "What is the capital concept behind {topic}?",
    "How many components does {topic} have?",
    "Explain how {topic} relates to {topic2} and compare their trade-offs.",
    "Compare and contrast {topic} with {topic2}, then summarize when to use each.",
    "Walk me through how {topic} works and why it matters for {topic2}.",
    "Explain the main failure modes of {topic} and how practitioners mitigate them.",
    "Analyze the relationship between {topic} and {topic2} with concrete examples.",
    "Describe how to combine {topic} and {topic2} in a real project, step by step.",
    "Prove, from first principles, the convergence properties underlying {topic}, "
    "and critique the standard assumptions in depth.",
    "Design a novel research methodology combining {topic} and {topic2}; derive its "
    "theoretical limits and propose an evaluation protocol for an open problem.",
    "Derive the governing equations of {topic} step by step, analyze the edge cases "
    "where they break down, and propose a publishable extension to the frontier.",
    "Critically evaluate the state-of-the-art research on {topic}, identify an open "
    "problem, and sketch a novel proof strategy with detailed error analysis.",
]


def prompt_text(rng, n_chars: int) -> str:
    """Exactly ``n_chars`` ASCII characters: a request tag unique to the
    draw, then questions from one domain, cut to length."""
    domain = DOMAINS[rng.choice(sorted(DOMAINS))]
    parts = [f"Ticket {rng.getrandbits(40):010x}:"]
    size = len(parts[0])
    while size < n_chars:
        t = rng.choice(TEMPLATES).format(topic=rng.choice(domain),
                                         topic2=rng.choice(domain))
        parts.append(t)
        size += len(t) + 1
    text = " ".join(parts)[:n_chars]
    assert text.isascii() and len(text) == n_chars
    return text
