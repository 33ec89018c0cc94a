// Generated by repro.codegen (paper Table I) — spec nn_3i_4x4_2o

// cell=mlp steps=4 unroll=1 c_slow=1 width=16

module Create_mult #(parameter WIDTH = 16) (
  input  wire                      clk,
  input  wire                      en,
  input  wire                      clr,
  input  wire signed [WIDTH-1:0]   a,     // datapath operand
  input  wire signed [WIDTH-1:0]   w,     // coefficient (ROM port)
  output reg  signed [2*WIDTH-1:0] acc    // wide accumulator
);
  always @(posedge clk) begin
    if (clr)     acc <= {2*WIDTH{1'b0}};
    else if (en) acc <= acc + a * w;
  end
endmodule

module Create_AF_tanh #(parameter WIDTH = 16) (
  input  wire                    clk,
  input  wire signed [WIDTH-1:0] x,     // Q(4.12) MACC result
  output reg  signed [WIDTH-1:0] y
);
  // ROM LUT: tanh sampled on [-4, 4), 64 entries.
  // addr = clamp(x, -4, 4) mapped linearly: bias by +4
  // (= 1 << WIDTH-2 in Q4.12), saturate to [0, 8), take the top
  // 6 magnitude bits.
  wire signed [WIDTH:0] biased = {x[WIDTH-1], x} + (1 <<< (WIDTH - 2));
  wire [5:0] addr =
      (biased < 0)                    ? 6'd0 :
      (biased >= (1 <<< (WIDTH - 1))) ? 6'd63 :
      biased[WIDTH-2 -: 6];
  always @(posedge clk) begin
    case (addr)
      6'd0: y <= 16'hf003;
      6'd1: y <= 16'hf004;
      6'd2: y <= 16'hf005;
      6'd3: y <= 16'hf007;
      6'd4: y <= 16'hf008;
      6'd5: y <= 16'hf00b;
      6'd6: y <= 16'hf00e;
      6'd7: y <= 16'hf012;
      6'd8: y <= 16'hf017;
      6'd9: y <= 16'hf01d;
      6'd10: y <= 16'hf026;
      6'd11: y <= 16'hf030;
      6'd12: y <= 16'hf03e;
      6'd13: y <= 16'hf050;
      6'd14: y <= 16'hf066;
      6'd15: y <= 16'hf082;
      6'd16: y <= 16'hf0a7;
      6'd17: y <= 16'hf0d5;
      6'd18: y <= 16'hf10f;
      6'd19: y <= 16'hf159;
      6'd20: y <= 16'hf1b5;
      6'd21: y <= 16'hf229;
      6'd22: y <= 16'hf2b9;
      6'd23: y <= 16'hf36a;
      6'd24: y <= 16'hf441;
      6'd25: y <= 16'hf544;
      6'd26: y <= 16'hf675;
      6'd27: y <= 16'hf7d8;
      6'd28: y <= 16'hf96a;
      6'd29: y <= 16'hfb28;
      6'd30: y <= 16'hfd09;
      6'd31: y <= 16'hff00;
      6'd32: y <= 16'h0100;
      6'd33: y <= 16'h02f7;
      6'd34: y <= 16'h04d8;
      6'd35: y <= 16'h0696;
      6'd36: y <= 16'h0828;
      6'd37: y <= 16'h098b;
      6'd38: y <= 16'h0abc;
      6'd39: y <= 16'h0bbf;
      6'd40: y <= 16'h0c96;
      6'd41: y <= 16'h0d47;
      6'd42: y <= 16'h0dd7;
      6'd43: y <= 16'h0e4b;
      6'd44: y <= 16'h0ea7;
      6'd45: y <= 16'h0ef1;
      6'd46: y <= 16'h0f2b;
      6'd47: y <= 16'h0f59;
      6'd48: y <= 16'h0f7e;
      6'd49: y <= 16'h0f9a;
      6'd50: y <= 16'h0fb0;
      6'd51: y <= 16'h0fc2;
      6'd52: y <= 16'h0fd0;
      6'd53: y <= 16'h0fda;
      6'd54: y <= 16'h0fe3;
      6'd55: y <= 16'h0fe9;
      6'd56: y <= 16'h0fee;
      6'd57: y <= 16'h0ff2;
      6'd58: y <= 16'h0ff5;
      6'd59: y <= 16'h0ff8;
      6'd60: y <= 16'h0ff9;
      6'd61: y <= 16'h0ffb;
      6'd62: y <= 16'h0ffc;
      6'd63: y <= 16'h0ffd;
      default: y <= {WIDTH{1'b0}};
    endcase
  end
endmodule

module Create_Layer_hidden_z #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(4)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [4*WIDTH-1:0]  x_bus,  // input bus (4 lanes)
  output wire signed [4*WIDTH-1:0] z_bus,  // MACC results (4 lanes)
  output reg                         done
);
  // coefficient ROM: 4 page(s) x 4x4 words
  reg signed [WIDTH-1:0] rom [0:63];
  reg signed [WIDTH-1:0] rom_b [0:15];  // bias ROM, one word per lane
  initial begin
    rom[0] = 16'h0197;
    rom[1] = 16'hf9d7;
    rom[2] = 16'hf5ee;
    rom[3] = 16'hfcb7;
    rom[4] = 16'hf7f4;
    rom[5] = 16'h024b;
    rom[6] = 16'h07f5;
    rom[7] = 16'h03fd;
    rom[8] = 16'hfa0d;
    rom[9] = 16'h091c;
    rom[10] = 16'hffec;
    rom[11] = 16'h0399;
    rom[12] = 16'hfd9e;
    rom[13] = 16'hfbec;
    rom[14] = 16'h0237;
    rom[15] = 16'h05fd;
    rom[16] = 16'hfe5c;
    rom[17] = 16'hf93d;
    rom[18] = 16'h07ce;
    rom[19] = 16'h044c;
    rom[20] = 16'hf862;
    rom[21] = 16'h072f;
    rom[22] = 16'h046d;
    rom[23] = 16'hef2a;
    rom[24] = 16'hf436;
    rom[25] = 16'h006a;
    rom[26] = 16'h0c97;
    rom[27] = 16'hf8e2;
    rom[28] = 16'hfec2;
    rom[29] = 16'hfe79;
    rom[30] = 16'h0351;
    rom[31] = 16'hffe2;
    rom[32] = 16'hef12;
    rom[33] = 16'h06a1;
    rom[34] = 16'h09f4;
    rom[35] = 16'h075e;
    rom[36] = 16'h0de0;
    rom[37] = 16'hfcd6;
    rom[38] = 16'h02d8;
    rom[39] = 16'hfff6;
    rom[40] = 16'h0d42;
    rom[41] = 16'h1177;
    rom[42] = 16'h010e;
    rom[43] = 16'h0337;
    rom[44] = 16'hf8f4;
    rom[45] = 16'hfe18;
    rom[46] = 16'hfc8a;
    rom[47] = 16'h0365;
    rom[48] = 16'hfa4b;
    rom[49] = 16'h1000;
    rom[50] = 16'h02f0;
    rom[51] = 16'h045c;
    rom[52] = 16'hffca;
    rom[53] = 16'h0920;
    rom[54] = 16'hfd64;
    rom[55] = 16'h02bd;
    rom[56] = 16'h0918;
    rom[57] = 16'h0639;
    rom[58] = 16'h0943;
    rom[59] = 16'h0eac;
    rom[60] = 16'hfd69;
    rom[61] = 16'h06ca;
    rom[62] = 16'hf7d6;
    rom[63] = 16'hed05;
  end
  initial begin
    rom_b[0] = 16'hfddd;
    rom_b[1] = 16'hff70;
    rom_b[2] = 16'h007f;
    rom_b[3] = 16'hfe66;
    rom_b[4] = 16'hffe0;
    rom_b[5] = 16'hfe8b;
    rom_b[6] = 16'h006d;
    rom_b[7] = 16'h02e7;
    rom_b[8] = 16'hff9c;
    rom_b[9] = 16'hfe7a;
    rom_b[10] = 16'h01aa;
    rom_b[11] = 16'h018d;
    rom_b[12] = 16'h0259;
    rom_b[13] = 16'h0020;
    rom_b[14] = 16'hff56;
    rom_b[15] = 16'h013b;
  end
  reg [$clog2(4):0] cyc;  // 4 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 4; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 4);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 4)*WIDTH +: WIDTH]),
          .w(rom[k*16 + gi*4 + (idx % 4)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH] + rom_b[k*4 + gi];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 3);
    end
  end
endmodule

module Create_Layer_beta #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(2)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [3*WIDTH-1:0]  x_bus,  // input bus (3 lanes)
  output wire signed [4*WIDTH-1:0] z_bus,  // MACC results (4 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 4x3 words
  reg signed [WIDTH-1:0] rom [0:11];
  initial begin
    rom[0] = 16'h0946;
    rom[1] = 16'hf7a1;
    rom[2] = 16'hf917;
    rom[3] = 16'hf52e;
    rom[4] = 16'hf7f4;
    rom[5] = 16'h0571;
    rom[6] = 16'h06b0;
    rom[7] = 16'hf687;
    rom[8] = 16'h0f59;
    rom[9] = 16'hee88;
    rom[10] = 16'hf418;
    rom[11] = 16'h013c;
  end
  reg [$clog2(3):0] cyc;  // 3 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 4; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 3);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 3)*WIDTH +: WIDTH]),
          .w(rom[gi*3 + (idx % 3)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 2);
    end
  end
endmodule

module Create_Layer_End_C #(parameter WIDTH = 16, parameter J = 1) (
  input  wire                        clk,
  input  wire                        start,
  input  wire [$clog2(2)-1:0]        k,      // FSM step (ROM page select)
  input  wire signed [4*WIDTH-1:0]  x_bus,  // input bus (4 lanes)
  output wire signed [2*WIDTH-1:0] z_bus,  // MACC results (2 lanes)
  output reg                         done
);
  // coefficient ROM: 1 page(s) x 2x4 words
  reg signed [WIDTH-1:0] rom [0:7];
  initial begin
    rom[0] = 16'h0a5d;
    rom[1] = 16'h0ad7;
    rom[2] = 16'hfcb9;
    rom[3] = 16'hf9d3;
    rom[4] = 16'h030c;
    rom[5] = 16'h0027;
    rom[6] = 16'h0499;
    rom[7] = 16'hf9f9;
  end
  reg [$clog2(4):0] cyc;  // 4 serial MACC cycles (J = 1 copies)
  genvar gi, ji;
  generate
    for (gi = 0; gi < 2; gi = gi + 1) begin : lane
      // J parallel Create_mult copies stride the input bus; term ji covers
      // element cyc*J + ji (zero-padded past in_width), summed combinationally
      wire signed [2*WIDTH-1:0] acc [0:J-1];
      wire signed [2*WIDTH-1:0] acc_sum [0:J];
      assign acc_sum[0] = {2*WIDTH{1'b0}};
      for (ji = 0; ji < J; ji = ji + 1) begin : copy
        wire [31:0] idx = cyc * J + ji;
        wire        pad = (idx >= 4);
        Create_mult #(.WIDTH(WIDTH)) u_mult (
          .clk(clk), .en(~done & ~pad), .clr(start),
          .a(x_bus[(idx % 4)*WIDTH +: WIDTH]),
          .w(rom[gi*4 + (idx % 4)]),
          .acc(acc[ji])
        );
        assign acc_sum[ji+1] = acc_sum[ji] + acc[ji];
      end
      assign z_bus[gi*WIDTH +: WIDTH] = acc_sum[J][2*WIDTH-1-4 -: WIDTH];  // Q-align
    end
  endgenerate
  always @(posedge clk) begin
    if (start) begin cyc <= 0; done <= 1'b0; end
    else if (!done) begin
      cyc  <= cyc + 1;
      done <= (cyc == 3);
    end
  end
endmodule

module Create_Datapath_hidden #(parameter WIDTH = 16) (
  input  wire clk,
  input  wire start,
  input  wire load,
  input  wire [$clog2(4)-1:0] k,
  input  wire signed [4*WIDTH-1:0] x_init,
  output wire signed [4*WIDTH-1:0] x_bus,
  output wire step_done
);
  reg signed [4*WIDTH-1:0] r_x;  // state register
  wire signed [4*WIDTH-1:0] w_x;  assign w_x = r_x;
  // const ROM 'W' [4x4] (per-step pages)
  // const ROM 'b' [1x4] (per-step pages)
  wire signed [4*WIDTH-1:0] w_z;
  wire d_z;
  Create_Layer_hidden_z #(.WIDTH(WIDTH)) u_z (
    .clk(clk), .start(start), .k(k),
    .x_bus(w_x), .z_bus(w_z), .done(d_z)
  );  // 4 -> 4 MACC array + bias ROM
  wire signed [4*WIDTH-1:0] w_x_next;
  genvar ai_x_next;
  generate
    for (ai_x_next = 0; ai_x_next < 4; ai_x_next = ai_x_next + 1) begin : af_x_next
      Create_AF_tanh #(.WIDTH(WIDTH)) u_x_next (.clk(clk),
        .x(w_z[ai_x_next*WIDTH +: WIDTH]), .y(w_x_next[ai_x_next*WIDTH +: WIDTH]));
    end
  endgenerate
  assign step_done = d_z;
  // ONE register write-back per start kick (step_done is a sticky level
  // that only clears on the next start pulse).  AF_DEPTH settle cycles let
  // the registered AF ROM chain propagate the FINAL MACC sum (one clock per
  // chained ROM) before the state registers latch.
  localparam AF_DEPTH = 1;
  reg stepped;
  reg [2:0] af_wait;
  always @(posedge clk) begin
    if (load) begin
      stepped <= 1'b0; af_wait <= 3'd0;
      r_x <= x_init;
    end else if (start) begin
      stepped <= 1'b0; af_wait <= 3'd0;
    end else if (step_done && af_wait < AF_DEPTH) begin
      af_wait <= af_wait + 3'd1;
    end else if (step_done && !stepped) begin
      stepped <= 1'b1;
      r_x <= w_x_next;
    end
  end
  assign x_bus = r_x;
endmodule

module Create_TopModule_nn_3i_4x4_2o #(parameter WIDTH = 16) (
  input  wire                       clk,
  input  wire                       rst,
  input  wire                       start,
  input  wire signed [3*WIDTH-1:0]   u_bus,
  output wire signed [2*WIDTH-1:0]  y_bus,
  output reg                        done
);
  // FSM: IDLE -> LOAD -> ITERATE x 4 -> READOUT -> DONE
  localparam S_IDLE = 3'd0, S_LOAD = 3'd1, S_ITER = 3'd2,
             S_READ = 3'd3, S_DONE = 3'd4;
  localparam STEPS = 4, CSLOW = 1, J = 1;
  localparam SETTLE = 3;  // last stage AF chain + write-back

  reg [2:0] fsm_state;
  reg [$clog2(4)-1:0] fsm_k;  // the time-multiplex counter
  // MACC layers treat start as a synchronous clear, so every use is kicked
  // by a ONE-CYCLE pulse; transitions qualify on !kick to let the sticky
  // done levels clear after each kick.
  reg kick;        // per-step start pulse into the first stage datapath
  reg load_kick;   // input-injection start (Create_Layer1)
  reg read_kick;   // readout start (Create_Layer_End)
  reg [2:0] settle;  // AF-ROM chain + write-back cycles before advancing
  wire step_start = kick;
  wire load       = (fsm_state == S_LOAD);
  wire signed [4*WIDTH-1:0] hidden_x;
  wire signed [4*WIDTH-1:0] hidden_x_init = x0_bus;
  wire done_hidden;
  Create_Datapath_hidden #(.WIDTH(WIDTH)) u_hidden (
    .clk(clk), .start(step_start), .load(load), .k(fsm_k),
    .x_init(hidden_x_init),
    .x_bus(hidden_x),
    .step_done(done_hidden)
  );
  reg done_hidden_q;
  always @(posedge clk) done_hidden_q <= done_hidden;
  wire step_done_all = done_hidden & ~done_hidden_q;
  // Create_Layer1: the beta u delta[k] input injection -> loaded state x0
  wire signed [4*WIDTH-1:0] x0_bus;
  wire load_done;
  Create_Layer_beta #(.WIDTH(WIDTH)) u_layer1 (
    .clk(clk), .start(load_kick), .k(1'b0),
    .x_bus(u_bus), .z_bus(x0_bus), .done(load_done)
  );
  // Create_Layer_End: readout y = C x[N] on the final carry
  wire signed [4*WIDTH-1:0] x_final = hidden_x;
  wire read_done;
  Create_Layer_End_C #(.WIDTH(WIDTH)) u_readout (
    .clk(clk), .start(read_kick), .k(1'b0),
    .x_bus(x_final), .z_bus(y_bus), .done(read_done)
  );
  always @(posedge clk) begin
    if (rst) begin
      fsm_state <= S_IDLE; fsm_k <= 0; done <= 1'b0;
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0; settle <= 3'd0;
    end else begin
      kick <= 1'b0; load_kick <= 1'b0; read_kick <= 1'b0;
      case (fsm_state)
        S_IDLE: if (start) begin fsm_state <= S_LOAD; load_kick <= 1'b1; end
        S_LOAD: if (load_done && !load_kick) begin
          fsm_state <= S_ITER; fsm_k <= 0; kick <= 1'b1;
        end
        S_ITER: begin
          // done EDGE -> SETTLE cycles (AF ROM chain, then register
          // write-back) -> next kick / readout
          if (settle == SETTLE) begin
            settle <= 3'd0;
            if (fsm_k == STEPS - 1) begin fsm_state <= S_READ; read_kick <= 1'b1; end
            else begin fsm_k <= fsm_k + 1; kick <= 1'b1; end  // next use
          end else if (settle != 3'd0) begin
            settle <= settle + 3'd1;
          end else if (step_done_all) begin
            settle <= 3'd1;
          end
        end
        S_READ: if (read_done && !read_kick) fsm_state <= S_DONE;
        S_DONE: begin done <= 1'b1; fsm_state <= S_IDLE; end
      endcase
    end
  end
endmodule
